"""The trace reducer: interval arithmetic, nesting, collectives and idle
gaps, on device timelines shaped as a TPU trace gives them (ops named by
their HLO text, loops enclosing their bodies)."""
import pytest

import bench_cells  # noqa: F401  (puts the checkout on the path)
from bench import tracing


def test_union_merges_and_clips():
    s, e = tracing.union([5, 0, 2, 20], [8, 3, 4, 30], t0=1, t1=25)
    assert s.tolist() == [1, 5, 20] and e.tolist() == [4, 8, 25]
    assert tracing.measure(s, e) == 3 + 3 + 5


def test_subtract_leaves_what_the_other_set_does_not_cover():
    a = tracing.union([0, 10], [5, 20])
    b = tracing.union([3, 12, 18], [11, 15, 30])
    assert tracing.subtract(a, b) == 3 + 4
    assert tracing.subtract(a, tracing.union([], [])) == 15


def _device(ops):
    return tracing._device("/device:TPU:0", [(s, e, f"%{n} = f32[] op()")
                                             for s, e, n in ops],
                           [(0, 100, "jit_x(1)")])


def test_exposed_collectives_and_idle_share():
    dev = _device([(0, 10, "fusion.1"), (10, 30, "all-reduce.3"),
                   (20, 25, "fusion.2"), (40, 50, "collective-permute-done"),
                   (55, 70, "while.4"), (56, 60, "all-gather.5")])
    tr = tracing.Trace([dev], [("bench.check", 30, 40)], 0.0, 100.0,
                       [("PjitFunction(f)", 70, 99)])
    # the while loop only encloses the all-gather: it is exposed too
    assert tracing.exposed_collective_s(tr, dev) == pytest.approx(29e-9)
    assert tracing.idle_share_pct(tr) == pytest.approx(45.0)
    gaps = tracing.idle_gaps(tr)
    assert gaps[0] == ["host:PjitFunction(f)", pytest.approx(30e-9)]
    assert gaps[1] == ["check", pytest.approx(10e-9)]
    assert ["none", pytest.approx(5e-9)] in gaps
    ops = dict(tracing.top_ops(tr))
    assert ops["all-reduce.3"] == pytest.approx(15e-9)  # fusion.2 inside
    assert ops["while.4"] == pytest.approx(11e-9)   # self time only


def test_a_recorded_trace_loads_with_its_window_and_spans(tmp_path):
    """A trace recorded here, on the CPU: the loader finds the harness's
    window and spans; with no device plane, no device metric is read."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    jax.block_until_ready(f(x))
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=tracing.profiler_options())
    with tracing.span("window"):
        for _ in range(2):
            with tracing.span("solve"):
                jax.block_until_ready(f(x))
    jax.profiler.stop_trace()
    tr = tracing.load(tracing.find_xplane(str(tmp_path)))
    assert 0 < tr.window_s < 60
    assert [s[0] for s in tr.spans].count("bench.solve") == 2
    assert tr.devices == [] and tracing.idle_share_pct(tr) is None
    assert tracing.top_ops(tr) == [] and tracing.idle_gaps(tr) == []
