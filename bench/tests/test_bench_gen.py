"""The benchmark's generators: the graph at the program's sizes equals the
program's own for the same seed, LDBC's relationships are added without
changing the rest, and each short read walks what LDBC says it walks."""
import numpy as np
import pytest

from bench.gen import graphs, paths, traffic
from bench.gen.graphs import (CONTAINED_IN, CONTAINER_OF, CREATED,
                              HAS_CREATOR, HAS_MODERATOR, KNOWS, REPLIED_BY,
                              REPLY_OF)

COUNTS = {"person": 300, "forum": 200, "post": 3000, "comment": 6000,
          "other": 50}


@pytest.fixture(scope="module")
def snb():
    return graphs.snb_graph(COUNTS, knows_mean_deg=5, seed=11,
                            ldbc_reads=True)


def _same_graph(a, b):
    assert np.array_equal(a.indptr, b.indptr)
    assert np.array_equal(a.indices, b.indices)
    assert np.array_equal(a.edge_types, b.edge_types)
    assert np.array_equal(a.node_types, b.node_types)


def test_snb_graph_at_the_programs_sizes_is_snb_like():
    from repro.graph import snb_like

    ours = graphs.snb_graph(graphs.counts_for_scale(1), 12, seed=3)
    prog = snb_like(1, seed=3)
    _same_graph(ours.graph, prog.graph)
    for k in ("persons", "posts", "comments", "forums"):
        assert np.array_equal(getattr(ours, k), getattr(prog, k))


@pytest.mark.parametrize("seed", [0, 2**31 + 11])
def test_hash_sharding(seed):
    from repro.graph import make_sharding, ogb_like

    g = ogb_like(500, 4, seed=1)
    with np.errstate(over="ignore"):
        assert np.array_equal(graphs.hash_partition(500, 6, seed),
                              make_sharding("hash", g, 6, seed=seed))


def _edges(g, etype):
    src = np.repeat(np.arange(g.n_nodes), np.diff(g.indptr))
    m = g.edge_types == etype
    return set(zip(src[m].tolist(), g.indices[m].tolist()))


def test_ldbc_reads_add_their_relationships_and_change_nothing_else(snb):
    plain = graphs.snb_graph(COUNTS, knows_mean_deg=5, seed=11)
    g, p = snb.graph, plain.graph
    for et in (KNOWS, CREATED, REPLY_OF, CONTAINER_OF, HAS_CREATOR):
        assert _edges(g, et) == _edges(p, et)
    assert _edges(g, CONTAINED_IN) == {(b, a) for a, b in
                                       _edges(g, CONTAINER_OF)}
    assert _edges(g, REPLIED_BY) == {(b, a) for a, b in _edges(g, REPLY_OF)}
    mods = _edges(g, HAS_MODERATOR)
    assert sorted(f for f, _ in mods) == snb.forums.tolist()
    assert all(m in set(snb.persons.tolist()) for _, m in mods)
    rel = snb.relationships()
    assert rel["hasModerator"] == COUNTS["forum"]
    assert rel["hasCreator"] == COUNTS["post"] + COUNTS["comment"]
    assert rel["replyOf"] == COUNTS["comment"]
    assert rel["containerOf"] == COUNTS["post"]
    n = sum(COUNTS.values())
    assert g.n_nodes == n
    other = np.arange(n - COUNTS["other"], n)
    assert (np.diff(g.indptr)[other] == 0).all()
    assert not np.isin(g.indices, other).any()


def _post_of(g, m):
    while True:
        up = g.neighbors_typed(m, REPLY_OF)
        if not len(up):
            return m
        m = int(up[0])


@pytest.mark.parametrize("tmpl", ["IS2", "IS3", "IS5", "IS6", "IS7"])
def test_each_short_read_walks_what_ldbc_says(snb, tmpl):
    g = snb.graph
    rng = np.random.default_rng(4)
    pool = snb.persons if tmpl in paths.PERSON_READS else snb.messages
    seen = 0
    for root in rng.choice(pool, 60):
        root = int(root)
        for p in paths.short_read_paths(snb, root, tmpl, rng, 64):
            assert p[0] == root
            if len(p) == 1:
                continue
            seen += 1
            if tmpl == "IS3":
                assert len(p) == 2 and p[1] in g.neighbors_typed(root, KNOWS)
            elif tmpl == "IS5":
                assert p[1:] == [int(g.neighbors_typed(root, HAS_CREATOR)[0])]
            elif tmpl == "IS7":
                assert p[1] in g.neighbors_typed(root, REPLIED_BY)
                assert p[2] == int(g.neighbors_typed(p[1], HAS_CREATOR)[0])
            else:
                msg = p[1] if tmpl == "IS2" else root
                if tmpl == "IS2":
                    assert msg in g.neighbors_typed(root, CREATED)
                chain = p[p.index(msg):]
                post = _post_of(g, msg)
                k = chain.index(post)
                for a, b in zip(chain[:k], chain[1:k + 1]):
                    assert b == int(g.neighbors_typed(a, REPLY_OF)[0])
                if tmpl == "IS2":
                    assert chain[k + 1:] == [
                        int(g.neighbors_typed(post, HAS_CREATOR)[0])]
                else:
                    forum = int(g.neighbors_typed(post, CONTAINED_IN)[0])
                    assert chain[k + 1:] == [
                        forum, int(g.neighbors_typed(forum, HAS_MODERATOR)[0])]
    assert seen


def test_a_path_longer_than_max_len_is_cut(snb):
    g = snb.graph
    deep = max(snb.comments.tolist(),
               key=lambda c: len(paths._to_post(g, c, 64)))
    full = paths.short_read_paths(snb, deep, "IS6", None, 64)[0]
    assert len(full) > 4
    assert paths.short_read_paths(snb, deep, "IS6", None, 4) == [full[:4]]


def test_provision_calls_have_fixed_shapes_and_distinct_paths(snb):
    shard = graphs.hash_partition(snb.graph.n_nodes, 6)
    data = {"snb": snb, "shard": shard}
    quota = {"IS2": 40, "IS3": 30, "IS5": 5, "IS6": 5, "IS7": 4}
    spec = {"driver": "provision", "distinct_calls": 2, "paths": {
        "kind": "snb_short_reads",
        "mix": {k: 1 for k in quota}, "paths_per_template": quota}}
    calls = traffic.provision_calls(data, spec, 8, seed=2**40 + 3)
    assert [c[0].shape for c in calls] == [(84, 8), (84, 8)]
    for o, ln, _ in calls:
        key = traffic.path_key(o, ln, shard)
        assert len(np.unique(key, axis=0)) == len(o)
    again = traffic.provision_calls(data, spec, 8, seed=2**40 + 3)
    assert all(np.array_equal(a[0], b[0]) for a, b in zip(calls, again))
