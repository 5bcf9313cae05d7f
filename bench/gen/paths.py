"""Causal access paths of the LDBC SNB Interactive short reads.

Each read is the objects it touches, in the order it touches them, one
path per chain of accesses (LDBC SNB Interactive v1 specification, short
reads IS2, IS3, IS5, IS6, IS7):

* IS2 (a person): 10 of the person's messages; for each, the message, its
  ``replyOf`` chain up to the original post, and that post's creator;
* IS3 (a person): each friend (``knows``);
* IS5 (a message): its creator;
* IS6 (a message): its ``replyOf`` chain up to the post, the forum that
  contains the post, and the forum's moderator;
* IS7 (a message): each reply to it and the reply's creator.

The graph has no creation dates, so IS2's 10 messages are drawn at
random.  A path longer than ``max_len`` objects (a conversation deeper
than the path can hold) is cut to its first ``max_len``.
"""
from __future__ import annotations

import numpy as np

from bench.gen.graphs import (CONTAINED_IN, CREATED, HAS_CREATOR,
                              HAS_MODERATOR, KNOWS, REPLIED_BY, REPLY_OF)

PERSON_READS = ("IS2", "IS3")
MESSAGE_READS = ("IS5", "IS6", "IS7")


def _first(g, v: int, etype: int):
    nbr = g.neighbors_typed(v, etype)
    return int(nbr[0]) if len(nbr) else None


def _to_post(g, message: int, max_len: int) -> list[int]:
    """The message and its ``replyOf`` chain, ending at the original post
    (or after ``max_len`` objects)."""
    chain = [message]
    cur = _first(g, message, REPLY_OF)
    while cur is not None and len(chain) < max_len:
        chain.append(cur)
        cur = _first(g, cur, REPLY_OF)
    return chain


def _is2(g, person, rng, max_len, k_messages=10):
    msgs = g.neighbors_typed(person, CREATED)
    if len(msgs) == 0:
        return [[person]]
    take = rng.choice(msgs, size=min(k_messages, len(msgs)), replace=False)
    out = []
    for m in take:
        chain = _to_post(g, int(m), max_len)
        creator = _first(g, chain[-1], HAS_CREATOR)
        out.append([person] + chain + ([creator] if creator is not None
                                       else []))
    return out


def _is3(g, person, rng, max_len):
    return [[person, int(f)] for f in g.neighbors_typed(person, KNOWS)] or [
        [person]]


def _is5(g, message, rng, max_len):
    creator = _first(g, message, HAS_CREATOR)
    return [[message] + ([creator] if creator is not None else [])]


def _is6(g, message, rng, max_len):
    path = _to_post(g, message, max_len)
    forum = _first(g, path[-1], CONTAINED_IN)
    if forum is not None:
        path.append(forum)
        moderator = _first(g, forum, HAS_MODERATOR)
        if moderator is not None:
            path.append(moderator)
    return [path]


def _is7(g, message, rng, max_len):
    out = []
    for r in g.neighbors_typed(message, REPLIED_BY):
        creator = _first(g, int(r), HAS_CREATOR)
        out.append([message, int(r)] + ([creator] if creator is not None
                                        else []))
    return out or [[message]]


READS = {"IS2": _is2, "IS3": _is3, "IS5": _is5, "IS6": _is6, "IS7": _is7}


def short_read_paths(snb, root: int, template: str, rng,
                     max_len: int) -> list[list[int]]:
    """Paths of one short read rooted at ``root`` (a person for IS2 and
    IS3, a message for the others), each at most ``max_len`` objects."""
    return [p[:max_len] for p in READS[template](snb.graph, root, rng,
                                                 max_len)]
