"""The one traffic generator: reads a traffic file's parameters and draws
the operations of a run from its seed.

Provisioning traffic (``"paths"`` block) is a list of calls, each a fixed
number of distinct causal access paths: distinct under the paper's §5.3
rule (same root server, same tail), so the program's own pruning keeps
every one of them, and padded to the configuration's ``max_len``, so every
call hands the program the same shapes.

* ``snb_short_reads``: a stream of LDBC SNB short reads (template drawn
  from ``mix``, root uniform over the persons for IS2 and IS3 and over the
  messages for IS5, IS6 and IS7); each template's distinct paths are
  accepted in stream order until its quota in ``paths_per_template`` is
  full.
"""
from __future__ import annotations

import numpy as np

from bench.gen.paths import PERSON_READS, short_read_paths


def path_key(objects, lengths, shard) -> np.ndarray:
    """§5.3 identity of each path: root server, length, tail."""
    root_srv = shard[np.maximum(objects[:, 0], 0)].astype(np.int64)
    return np.concatenate(
        [root_srv[:, None], lengths[:, None].astype(np.int64),
         objects[:, 1:].astype(np.int64)], axis=1)


class _Acceptor:
    """Collects distinct paths (by §5.3 key) up to a quota."""

    def __init__(self, shard, max_len: int, quota: int):
        self.shard, self.L, self.quota = shard, max_len, quota
        self.seen: set[bytes] = set()
        self.rows: list[tuple[list[int], int]] = []

    @property
    def full(self) -> bool:
        return len(self.rows) >= self.quota

    def offer(self, paths, qid: int) -> None:
        for p in paths:
            if self.full:
                return
            if len(p) > self.L:
                raise ValueError(f"path of {len(p)} objects > max_len {self.L}")
            row = np.full((1, self.L), -1, np.int64)
            row[0, : len(p)] = p
            k = path_key(row, np.asarray([len(p)]), self.shard).tobytes()
            if k not in self.seen:
                self.seen.add(k)
                self.rows.append((p, qid))


def _arrays(rows, max_len):
    rows = sorted(rows, key=lambda r: r[1])
    objects = np.full((len(rows), max_len), -1, np.int32)
    lengths = np.zeros(len(rows), np.int32)
    qids = np.zeros(len(rows), np.int32)
    first = {}
    for i, (p, q) in enumerate(rows):
        objects[i, : len(p)] = p
        lengths[i] = len(p)
        qids[i] = first.setdefault(q, len(first))
    return objects, lengths, qids


def snb_call(snb, shard, spec: dict, max_len: int, rng):
    """One provisioning call's paths of SNB short reads."""
    mix = spec["mix"]
    templates = list(mix)
    probs = np.asarray([mix[t] for t in templates], np.float64)
    probs /= probs.sum()
    quota = spec["paths_per_template"]
    acc = {t: _Acceptor(shard, max_len, int(quota.get(t, 0)))
           for t in templates}
    messages = snb.messages
    qid = 0
    while not all(a.full for a in acc.values()):
        for c in rng.choice(len(templates), size=1024, p=probs):
            tmpl = templates[c]
            pool = snb.persons if tmpl in PERSON_READS else messages
            root = int(rng.choice(pool))
            if not acc[tmpl].full:
                acc[tmpl].offer(short_read_paths(snb, root, tmpl, rng,
                                                 max_len), qid)
            qid += 1
        if qid > 1000 * sum(int(v) for v in quota.values()) + 10_000:
            raise RuntimeError("path quotas cannot be filled from this graph")
    rows = [r for a in acc.values() for r in a.rows]
    return _arrays(rows, max_len)


def provision_calls(data: dict, traffic: dict, max_len: int, seed: int):
    """The distinct calls of a provisioning run, drawn from ``seed``."""
    spec = traffic["paths"]
    if spec["kind"] != "snb_short_reads":
        raise ValueError(f"unknown path kind {spec['kind']!r}")
    rng = np.random.default_rng([seed, 1])
    return [snb_call(data["snb"], data["shard"], spec, max_len, rng)
            for _ in range(int(traffic["distinct_calls"]))]
