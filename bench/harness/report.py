"""Counters, per-layer metrics and the compared numbers of a run."""
from __future__ import annotations

import json
import math
import os

from bench.harness import cell as cells

PEAKS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def counter_delta(before: dict, after: dict) -> dict:
    """Counters that moved between two registry snapshots."""
    out = {}
    for k, v in after.items():
        if isinstance(v, (int, float)):
            d = v - before.get(k, 0)
            if d:
                out[k] = d
    return out


def peaks(kind: str) -> dict:
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def per_layer(cell, trace, counters: dict, summary: dict):
    """Per-layer metrics of a traced run, device busy/window and the
    breakdown.  A reader that finds nothing returns None and its metric
    is left out."""
    from bench.harness.trace import busy_ns, idle_gaps, op_seconds

    spans = [(s, e) for n, s, e in trace.spans]
    if not spans:
        raise RuntimeError("the trace holds none of the harness's spans")
    window = [(min(s for s, _ in spans), max(e for _, e in spans))]
    ctx = {
        "trace": trace, "spans": spans, "counters": counters,
        "summary": summary, "cell": cell,
        "peaks": peaks(summary["device_kind"]),
    }
    metrics = {}
    for m in cell.per_layer:
        v = cells.metric_reader(m["name"]).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    busy = busy_ns(trace, window)
    extra = {
        "device": {"busy_s": busy / 1e9,
                   "window_s": (window[0][1] - window[0][0]) / 1e9},
        "breakdown": {"device_ops": op_seconds(trace, window),
                      "idle_gaps": idle_gaps(trace, window)},
        "layout": trace.layout,
    }
    return metrics, extra


def checks(numbers: dict, limits: dict) -> dict:
    """``{name: {"value", "limit"}}`` for every limit; a number the run
    could not produce is None, which is not correct."""
    out = {}
    for name, limit in limits.items():
        v = numbers.get(name)
        if isinstance(v, float) and not math.isfinite(v):
            v = None
        out[name] = {"value": v, "limit": limit}
    return out
