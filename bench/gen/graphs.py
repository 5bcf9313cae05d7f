"""The typed social graph of the SNB configurations, and the hash
sharding function.

``snb_graph`` is the program's ``repro.graph.generators.snb_like`` with
the per-type vertex counts and the mean ``knows`` degree made parameters
(``snb_like(scale)`` is ``snb_graph(counts_for_scale(scale))``, the same
draws in the same order; ``bench/tests`` pins the two), plus what LDBC's
message reads walk and ``snb_like`` lacks.  ``hash_partition`` is a copy
of ``repro.graph.partition``'s hash sharding.
"""
from __future__ import annotations

import dataclasses

import numpy as np

PERSON, POST, COMMENT, FORUM, OTHER = 0, 1, 2, 3, 4
KNOWS, CREATED, REPLY_OF, CONTAINER_OF, LIKES, HAS_CREATOR = 0, 1, 2, 3, 4, 5
# LDBC relationships that snb_like lacks, and the reverse directions of two
# it has (an edge type per direction, so that a walk reads one type)
HAS_MODERATOR, CONTAINED_IN, REPLIED_BY = 6, 7, 8


def counts_for_scale(scale: int) -> dict:
    """``snb_like(scale)``'s vertex counts."""
    return {"person": 3000 * scale, "forum": 800 * scale,
            "post": 12000 * scale, "comment": 30000 * scale, "other": 0}


@dataclasses.dataclass(frozen=True)
class Graph:
    """CSR adjacency: ``indices[indptr[v]:indptr[v + 1]]`` are v's
    out-neighbours, sorted; ``edge_types`` labels each entry."""

    indptr: np.ndarray
    indices: np.ndarray
    edge_types: np.ndarray
    node_types: np.ndarray

    @property
    def n_nodes(self) -> int:
        return int(self.indptr.shape[0]) - 1

    def neighbors_typed(self, v: int, etype: int) -> np.ndarray:
        lo, hi = self.indptr[v], self.indptr[v + 1]
        return self.indices[lo:hi][self.edge_types[lo:hi] == etype]


@dataclasses.dataclass(frozen=True)
class SNBGraph:
    graph: Graph
    persons: np.ndarray
    posts: np.ndarray
    comments: np.ndarray
    forums: np.ndarray

    @property
    def messages(self) -> np.ndarray:
        """Posts and comments: the roots of LDBC's message reads."""
        return np.concatenate([self.posts, self.comments])

    def relationships(self) -> dict:
        """Relationships held, each counted once, by LDBC name."""
        n = np.bincount(self.graph.edge_types, minlength=9)
        return {"knows": int(n[KNOWS]) // 2, "hasCreator": int(n[HAS_CREATOR]),
                "replyOf": int(n[REPLY_OF]),
                "containerOf": int(n[CONTAINER_OF]),
                "hasModerator": int(n[HAS_MODERATOR]), "likes": int(n[LIKES])}


def csr_from_edges(n_nodes, src, dst, edge_types, node_types) -> Graph:
    """Typed CSR of the (src, dst) pairs, sorted and de-duplicated."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    order = np.lexsort((dst, src))
    src, dst = src[order], dst[order]
    edge_types = np.asarray(edge_types)[order]
    keep = np.ones(len(src), dtype=bool)
    if len(src):
        keep[1:] = (src[1:] != src[:-1]) | (dst[1:] != dst[:-1])
    src, dst = src[keep], dst[keep]
    edge_types = edge_types[keep].astype(np.int16)
    indptr = np.zeros(n_nodes + 1, dtype=np.int64)
    np.add.at(indptr, src + 1, 1)
    indptr = np.cumsum(indptr)
    return Graph(
        indptr=indptr,
        indices=dst.astype(np.int32),
        edge_types=edge_types,
        node_types=np.asarray(node_types, np.int16),
    )


def _power_law_targets(rng, n_src, n_dst_pool, mean_deg, alpha=1.8,
                       dst_offset=0):
    deg = np.minimum(
        rng.zipf(alpha, size=n_src), max(4 * mean_deg, 8)
    ) + np.maximum(mean_deg - 1, 0)
    total = int(deg.sum())
    ranks = rng.zipf(1.4, size=total) % n_dst_pool
    src = np.repeat(np.arange(n_src, dtype=np.int64), deg)
    dst = ranks.astype(np.int64) + dst_offset
    return src, dst


def snb_graph(counts: dict, knows_mean_deg: int = 12, seed: int = 0,
              ldbc_reads: bool = False) -> SNBGraph:
    """SNB-like typed social graph with ``counts`` vertices of each type.

    Vertices are numbered persons, forums, posts, comments, then
    ``counts["other"]`` vertices (tags, places, organisations) that hold no
    relationship a short read walks.  As ``snb_like``: ``knows`` is drawn
    by a power law over the persons, each message has a uniform creator,
    a comment replies to a uniform post (0.6) or to a uniform earlier
    comment, each post lies in a uniform forum, and each person likes
    posts by a power law.  ``ldbc_reads`` adds, drawn after all of that
    (the rest is unchanged), each forum's moderator (a uniform person) and
    the reverse directions of ``containerOf`` (post to its forum) and
    ``replyOf`` (message to its replies), which LDBC IS6 and IS7 walk.
    """
    rng = np.random.default_rng(seed)
    n_person = int(counts["person"])
    n_forum = int(counts["forum"])
    n_post = int(counts["post"])
    n_comment = int(counts["comment"])

    p0 = 0
    f0 = n_person
    o0 = f0 + n_forum
    c0 = o0 + n_post
    n_msg_end = c0 + n_comment
    n = n_msg_end + int(counts.get("other", 0))

    node_types = np.empty(n, dtype=np.int16)
    node_types[p0:f0] = PERSON
    node_types[f0:o0] = FORUM
    node_types[o0:c0] = POST
    node_types[c0:n_msg_end] = COMMENT
    node_types[n_msg_end:] = OTHER

    srcs, dsts, etys = [], [], []

    def add(src, dst, et):
        srcs.append(src)
        dsts.append(dst)
        etys.append(np.full(len(src), et, np.int16))

    s, d = _power_law_targets(rng, n_person, n_person,
                              mean_deg=knows_mean_deg)
    keep = s != d
    add(s[keep], d[keep], KNOWS)
    add(d[keep], s[keep], KNOWS)

    post_creator = rng.integers(0, n_person, n_post)
    add(post_creator, np.arange(o0, c0), CREATED)
    add(np.arange(o0, c0), post_creator, HAS_CREATOR)
    comment_creator = rng.integers(0, n_person, n_comment)
    add(comment_creator, np.arange(c0, n_msg_end), CREATED)
    add(np.arange(c0, n_msg_end), comment_creator, HAS_CREATOR)

    parent_is_post = rng.random(n_comment) < 0.6
    parent = np.where(
        parent_is_post,
        rng.integers(o0, c0, n_comment),
        c0 + rng.integers(0, np.maximum(np.arange(n_comment), 1)),
    )
    add(np.arange(c0, n_msg_end), parent, REPLY_OF)

    post_forum = rng.integers(f0, o0, n_post)
    add(post_forum, np.arange(o0, c0), CONTAINER_OF)

    s, d = _power_law_targets(rng, n_person, n_post, mean_deg=6, dst_offset=o0)
    add(s, d, LIKES)

    if ldbc_reads:
        add(np.arange(f0, o0), rng.integers(0, n_person, n_forum),
            HAS_MODERATOR)
        add(np.arange(o0, c0), post_forum, CONTAINED_IN)
        add(parent, np.arange(c0, n_msg_end), REPLIED_BY)

    graph = csr_from_edges(
        n,
        np.concatenate(srcs),
        np.concatenate(dsts),
        np.concatenate(etys),
        node_types,
    )
    return SNBGraph(
        graph=graph,
        persons=np.arange(p0, f0),
        posts=np.arange(o0, c0),
        comments=np.arange(c0, n_msg_end),
        forums=np.arange(f0, o0),
    )


def hash_partition(n_nodes: int, n_servers: int, seed: int = 0) -> np.ndarray:
    """``make_sharding("hash", ...)``: splittable-mix hash of the vertex id."""
    v = np.arange(n_nodes, dtype=np.uint64)
    z = v + np.uint64(seed) * np.uint64(0x9E3779B97F4A7C15) + np.uint64(1)
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    z = z ^ (z >> np.uint64(31))
    return (z % np.uint64(n_servers)).astype(np.int32)
