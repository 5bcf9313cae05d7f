"""Device trace of a traced run, and its reduction to busy time, idle gaps
and per-program device time.

``load`` reads the profiler's ``.xplane.pb`` with nothing but JAX into a
:class:`Trace` of numpy arrays; every reduction below works on a
:class:`Trace` alone, so a small recorded trace (``Trace.of``) tests it.

* device ops: the events of each device plane's ``XLA Ops`` line, each
  named by the ``XLA Modules`` event (the jitted program) that contains it
  (the ops' own stats are not read: at millions of ops they would take
  minutes);
* program runs: the events of each device plane's ``XLA Modules`` line,
  one per run of a jitted program;
* host spans: the ``jax.profiler.TraceAnnotation`` events the harness
  opens, named ``bench.<what>``.
"""
from __future__ import annotations

import dataclasses
import glob
import os

import numpy as np


@dataclasses.dataclass
class Trace:
    dev: np.ndarray       # int [N] device index of each op
    start: np.ndarray     # int64 [N] ns
    end: np.ndarray       # int64 [N] ns
    name: np.ndarray      # int [N] index into names
    prog: np.ndarray      # int [N] index into names ("" = no program)
    names: list
    spans: list           # (span name, start ns, end ns)
    layout: list          # (plane name, line name, events)
    mod_dev: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(0, np.int64))  # device of each run
    mod_start: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(0, np.int64))  # its start, ns

    @staticmethod
    def of(ops, spans, layout=(), modules=()) -> "Trace":
        """From (device, op name, program name, start, end) tuples, and
        (device, start) of each program run."""
        names: dict = {"": 0}
        idx = [(d, names.setdefault(n, len(names)),
                names.setdefault(p, len(names)), s, e)
               for d, n, p, s, e in ops]
        a = np.asarray(idx, np.int64).reshape(-1, 5)
        m = np.asarray(list(modules), np.int64).reshape(-1, 2)
        return Trace(a[:, 0], a[:, 3], a[:, 4], a[:, 1], a[:, 2],
                     list(names), list(spans), list(layout), m[:, 0], m[:, 1])


def load(directory: str) -> Trace:
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise RuntimeError(f"no .xplane.pb under {directory}")
    names: dict = {"": 0}
    cols: list = [[], [], [], [], []]
    mod_dev, mod_start = [], []
    spans, layout = [], []
    dev = 0
    for f in files:
        for plane in ProfileData.from_file(f).planes:
            is_device = (plane.name.startswith("/device:")
                         and "CPU" not in plane.name)
            ops, mods = [], []
            for line in plane.lines:
                n = 0
                for e in line.events:
                    n += 1
                    if is_device and line.name == "XLA Ops":
                        ops.append((e.name, e.start_ns, e.duration_ns))
                    elif is_device and line.name == "XLA Modules":
                        mods.append((e.start_ns, e.start_ns + e.duration_ns,
                                     e.name.split("(")[0]))
                    elif not is_device and e.name.startswith("bench."):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
                layout.append((plane.name, line.name, n))
            if not is_device:
                continue
            s = np.asarray([o[1] for o in ops], np.int64)
            cols[0].append(np.full(len(ops), dev))
            cols[1].append(s)
            cols[2].append(s + np.asarray([o[2] for o in ops], np.int64))
            cols[3].append(np.asarray([names.setdefault(o[0], len(names))
                                       for o in ops], np.int64))
            cols[4].append(_program_of(s, mods, names))
            mod_dev.append(np.full(len(mods), dev))
            mod_start.append(np.asarray([m[0] for m in mods], np.int64))
            dev += 1
    a = [np.concatenate(c) if c else np.zeros(0, np.int64)
         for c in cols + [mod_dev, mod_start]]
    return Trace(a[0], a[1], a[2], a[3], a[4], list(names), spans, layout,
                 a[5], a[6])


def _program_of(starts, mods, names) -> np.ndarray:
    """Index of the program (module event) containing each op start."""
    if not mods:
        return np.zeros(len(starts), np.int64)
    mods = sorted(mods)
    ms = np.asarray([m[0] for m in mods], np.int64)
    me = np.asarray([m[1] for m in mods], np.int64)
    mid = np.asarray([names.setdefault(m[2], len(names)) for m in mods],
                     np.int64)
    i = np.maximum(np.searchsorted(ms, starts, side="right") - 1, 0)
    inside = (ms[i] <= starts) & (me[i] >= starts)
    return np.where(inside, mid[i], 0)


def union(intervals) -> list:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> int:
    return sum(e - s for s, e in union(intervals))


def _merged(start, end):
    """Disjoint sorted (starts, ends) of the union of intervals (numpy)."""
    if not len(start):
        return start, end
    o = np.argsort(start, kind="stable")
    s, e = start[o], np.maximum.accumulate(end[o])
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > e[:-1]
    first = np.flatnonzero(new)
    last = np.r_[first[1:] - 1, len(s) - 1]
    return s[first], e[last]


def _clip_len(s, e, windows) -> int:
    tot = 0
    for ws, we in union(windows):
        tot += int(np.maximum(np.minimum(e, we) - np.maximum(s, ws), 0).sum())
    return tot


def busy_ns(trace: Trace, windows) -> float:
    """Device-busy time inside ``windows``, averaged over the devices."""
    devs = np.unique(trace.dev)
    if not len(devs):
        return 0.0
    tot = 0
    for d in devs:
        m = trace.dev == d
        tot += _clip_len(*_merged(trace.start[m], trace.end[m]), windows)
    return tot / len(devs)


def program_count(trace: Trace, windows) -> float:
    """Program runs that start inside ``windows``, averaged over the
    devices."""
    devs = np.unique(trace.mod_dev)
    if not len(devs):
        return 0.0
    inside = np.zeros(len(trace.mod_start), bool)
    for ws, we in union(windows):
        inside |= (trace.mod_start >= ws) & (trace.mod_start < we)
    return float(inside.sum()) / len(devs)


def idle_gaps(trace: Trace, windows, top: int = 10) -> list:
    """Longest gaps between device ops inside ``windows`` (device 0), each
    named by the host span that covers its midpoint and the program that
    ran last before it: ``[(name, seconds), ...]``."""
    m = trace.dev == 0
    order = np.argsort(trace.start[m], kind="stable")
    starts, prog = trace.start[m][order], trace.prog[m][order]
    bs, be = _merged(trace.start[m], trace.end[m])
    gaps = []
    for ws, we in union(windows):
        k = (be > ws) & (bs < we)
        s, e = bs[k], be[k]
        lo = np.r_[ws, e]
        hi = np.r_[s, we]
        lo = np.maximum(lo, ws)
        ok = hi > lo
        gaps += list(zip(lo[ok].tolist(), hi[ok].tolist()))
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (s + e) / 2
        span = min((sp for sp in trace.spans if sp[1] <= mid <= sp[2]),
                   key=lambda sp: sp[2] - sp[1], default=None)
        i = int(np.searchsorted(starts, s, side="right")) - 1
        after = trace.names[prog[i]] if i >= 0 and prog[i] else "start"
        named.append((f"{span[0] if span else 'outside spans'} after {after}",
                      (e - s) / 1e9))
    return named


def op_seconds(trace: Trace, windows, top: int = 10) -> list:
    """Device ops that took most time inside ``windows``, summed by program
    and op name: ``[(name, seconds), ...]`` (device 0)."""
    m = trace.dev == 0
    s, e = trace.start[m], trace.end[m]
    t = np.zeros(len(s))
    for ws, we in union(windows):
        t += np.maximum(np.minimum(e, we) - np.maximum(s, ws), 0)
    key = trace.prog[m] * len(trace.names) + trace.name[m]
    u, inv = np.unique(key, return_inverse=True)
    sums = np.bincount(inv, weights=t) if len(u) else np.zeros(0)
    out = []
    for j in np.argsort(-sums)[:top]:
        if sums[j] <= 0:
            break
        p, n = divmod(int(u[j]), len(trace.names))
        label = (f"{trace.names[p]}/{trace.names[n]}" if p
                 else trace.names[n])
        out.append((label, float(sums[j]) / 1e9))
    return out


def program_runs(trace: Trace, device: int = 0) -> list:
    """Per run of a program on ``device``: (program, start ns, end ns,
    summed op time ns), in time order.  Consecutive ops of one program
    form one run."""
    m = trace.dev == device
    o = np.argsort(trace.start[m], kind="stable")
    s, e, p = trace.start[m][o], trace.end[m][o], trace.prog[m][o]
    if not len(s):
        return []
    first = np.flatnonzero(np.r_[True, p[1:] != p[:-1]])
    busy = np.add.reduceat(e - s, first)
    ends = np.maximum.reduceat(e, first)
    return [(trace.names[p[f]], int(s[f]), int(ends[k]), int(busy[k]))
            for k, f in enumerate(first)]
