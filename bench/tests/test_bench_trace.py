"""The trace reduction on a small recorded trace."""
import jax
import numpy as np
import jax.numpy as jnp
import pytest

from bench.harness import trace as tr


def _trace():
    # device 0: program A (2 ops), a gap, program B, a gap, program A
    ops = [
        (0, "fusion.1", "jit_a", 100, 200),
        (0, "fusion.2", "jit_a", 150, 300),
        (0, "dot.3", "jit_b", 500, 700),
        (0, "fusion.1", "jit_a", 1000, 1100),
        (1, "fusion.1", "jit_a", 100, 1100),
    ]
    spans = [("bench.call", 50, 800), ("bench.call", 900, 1200)]
    return tr.Trace.of(ops, spans)


def test_union_and_length():
    assert tr.union([(5, 7), (1, 3), (2, 4)]) == [(1, 4), (5, 7)]
    assert tr.length([(0, 10), (5, 15)]) == 15


def test_busy_is_averaged_over_devices_inside_windows():
    t = _trace()
    win = [(s, e) for _, s, e in t.spans]
    # device 0: [100,300] + [500,700] + [1000,1100] = 500 inside spans
    # device 1: [100,800] + [900,1100] = 900
    assert tr.busy_ns(t, win) == (500 + 900) / 2


def test_idle_gaps_named_by_span_and_previous_program():
    t = _trace()
    gaps = tr.idle_gaps(t, [(50, 1200)])
    # longest: 700 -> 1000, its midpoint between the two spans
    assert gaps[0] == ("outside spans after jit_b", pytest.approx(300e-9))
    assert gaps[1] == ("bench.call after jit_a", pytest.approx(200e-9))
    assert sorted(g[1] for g in gaps) == pytest.approx(
        [50e-9, 100e-9, 200e-9, 300e-9])


def test_op_seconds_and_program_runs():
    t = _trace()
    top = dict(tr.op_seconds(t, [(0, 2000)]))
    assert top["jit_a/fusion.1"] == pytest.approx(200e-9)
    assert top["jit_b/dot.3"] == pytest.approx(200e-9)
    runs = tr.program_runs(t)
    assert [r[0] for r in runs] == ["jit_a", "jit_b", "jit_a"]
    assert runs[0][3] == 100 + 150


def test_load_finds_the_harness_spans(tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.test"):
        jnp.ones((64, 64)).sum().block_until_ready()
    jax.profiler.stop_trace()
    t = tr.load(str(tmp_path))
    assert [s[0] for s in t.spans] == ["bench.test"]
    assert t.spans[0][2] > t.spans[0][1]


def test_ops_are_named_by_the_program_that_contains_them():
    names = {"": 0}
    prog = tr._program_of(np.asarray([10, 30, 55, 80]),
                          [(50, 70, "jit_b"), (5, 45, "jit_a")], names)
    inv = {v: k for k, v in names.items()}
    assert [inv[int(p)] for p in prog] == ["jit_a", "jit_a", "jit_b", ""]


def test_program_runs_are_counted_inside_windows_per_device():
    t = tr.Trace.of([], [], modules=[(0, 100), (0, 500), (0, 1000),
                                     (1, 100), (1, 950)])
    assert tr.program_count(t, [(50, 800), (900, 1200)]) == (3 + 2) / 2
    assert tr.program_count(t, [(50, 800)]) == (2 + 1) / 2
    assert tr.program_count(tr.Trace.of([], []), [(0, 10)]) == 0.0


def test_programs_per_kpath_reader():
    from bench.harness import cell as cells

    reader = cells.metric_reader("greedy.programs_per_kpath.provision")
    t = tr.Trace.of([], [], modules=[(0, 100), (0, 500), (0, 1000)])
    ctx = {"trace": t, "spans": [(0, 2000)],
           "summary": {"paths_processed": 1500}}
    assert reader.read(ctx) == pytest.approx(2.0)
    # nothing to read: no paths, or no program in the spans
    assert reader.read(dict(ctx, summary={"paths_processed": 0})) is None
    assert reader.read(dict(ctx, spans=[(3000, 4000)])) is None


def test_load_counts_the_program_runs(tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    f = jax.jit(lambda x: x * 2 + 1)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.test"):
        for _ in range(3):
            x = f(x)
        x.block_until_ready()
    jax.profiler.stop_trace()
    t = tr.load(str(tmp_path))
    # the CPU has no device plane: no runs counted, and none made up
    assert len(t.mod_dev) == len(t.mod_start)
    if len(t.mod_start):
        assert tr.program_count(t, [(s, e) for _, s, e in t.spans]) >= 3
