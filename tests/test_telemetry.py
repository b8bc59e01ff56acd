"""Telemetry subsystem: the zero-overhead-when-disarmed contract
(bitwise-identical jaxprs, collective-count parity), in-graph convergence
histories, the uniform info schema, span trees and their place in the
profiler's trace, per-site communication bytes, the metrics registry, and
the report CLI."""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.core import api, pblas
from repro.telemetry import convergence, metrics, report


def _spd(n, rng, dtype=np.float32):
    a = rng.standard_normal((n, n)).astype(dtype)
    return (a @ a.T / n + 4 * np.eye(n)).astype(dtype)


def _sys(n, rng, spd=True):
    a = _spd(n, rng) if spd else (
        rng.standard_normal((n, n)).astype(np.float32)
        + n * np.eye(n, dtype=np.float32))
    b = rng.standard_normal(n).astype(np.float32)
    return jnp.asarray(a), jnp.asarray(b)


# --------------------------------------------------------------------------
# zero-overhead contract
# --------------------------------------------------------------------------

def _solve_fn(name, mesh1):
    # a FRESH closure per trace: jax caches jaxpr tracing on function
    # identity, and a cache hit would mask what arming actually traces
    # (arming is a trace-time decision — see docs/observability.md)
    return {
        "cg": lambda A, B: api.solve(A, B, method="cg", tol=1e-6),
        "ca_cg": lambda A, B: api.solve(A, B, method="ca_cg", tol=1e-6,
                                        s=2),
        "lu_spmd": lambda A, B: api.solve(A, B, method="lu", engine="spmd",
                                          mesh=mesh1, block_size=16),
    }[name]


@pytest.mark.parametrize("name", ["cg", "ca_cg", "lu_spmd"])
def test_disarmed_jaxpr_bitwise_identical(name, mesh1, rng):
    """A session that opened and closed must leave NO residue: the
    disarmed jaxpr after is byte-identical to the one before."""
    a, b = _sys(32, rng)
    before = str(jax.make_jaxpr(_solve_fn(name, mesh1))(a, b))
    with telemetry.session("t"):
        armed = str(jax.make_jaxpr(_solve_fn(name, mesh1))(a, b))
    after = str(jax.make_jaxpr(_solve_fn(name, mesh1))(a, b))
    assert before == after
    if name != "lu_spmd":
        # arming threads the residual ring buffer through the Krylov
        # loop carry — the armed graph must actually differ
        assert armed != before


def test_armed_adds_no_collectives(mesh1, rng):
    """Convergence recording is element-wise on replicated scalars: the
    armed spmd graph must trace the exact same collective tally."""
    a, b = _sys(64, rng)

    def tally():
        fn = lambda A, B: api.solve(A, B, method="cg", mesh=mesh1,
                                    engine="spmd", tol=1e-6)
        with pblas.collective_counts() as c:
            jax.make_jaxpr(fn)(a, b)
        return dict(c)

    base = tally()
    with telemetry.session("t"):
        armed = tally()
    assert armed == base
    assert base["psum"] > 0     # sanity: the tally saw the solve


def test_convergence_disarmed_is_none():
    assert convergence.init(jnp.float32(1.0), 1e-6) is None
    assert convergence.info(None) == {}
    assert not convergence.armed()


# --------------------------------------------------------------------------
# uniform info schema — every registered method
# --------------------------------------------------------------------------

@pytest.mark.parametrize("method", api.available_methods())
def test_info_schema_uniform(method, rng):
    n = 24
    a, b = _sys(n, rng, spd=True)
    kw = {"s": 2} if method.startswith("ca_") else {}
    r = api.solve(a, b, method=method, tol=1e-5, return_info=True, **kw)
    for key in ("fail_code", "fail_iter", "fail_reason"):
        assert key in r.info, (method, sorted(r.info))
    assert isinstance(r.info["fail_reason"], str)
    assert "residual_history" not in r.info     # disarmed: no history

    with telemetry.session("t"):
        r2 = api.solve(a, b, method=method, tol=1e-5, return_info=True,
                       **kw)
    assert "residual_history" in r2.info, method
    assert "iters_to_tol" in r2.info, method
    hist = np.asarray(r2.info["residual_history"])
    it = int(np.asarray(r2.info["iters_to_tol"]).max())
    if it >= 0:        # converged: history holds a finite initial residual
        assert np.isfinite(hist.reshape(-1)[0])


def test_iters_to_tol_matches_iterations(rng):
    a, b = _sys(48, rng, spd=True)
    with telemetry.session("t"):
        r = api.solve(a, b, method="cg", tol=1e-5, return_info=True)
    assert bool(r.converged)
    assert int(r.info["iters_to_tol"]) == int(r.iterations)
    hist = np.asarray(r.info["residual_history"])
    k = int(r.iterations)
    assert hist[0] > hist[min(k, hist.shape[0] - 1)]   # residual decreased


# --------------------------------------------------------------------------
# span tree + chrome trace + solve records
# --------------------------------------------------------------------------

def test_span_tree_and_solve_record(rng):
    a, b = _sys(24, rng, spd=True)
    with telemetry.session("t") as sess:
        api.solve(a, b, method="cg", tol=1e-5, return_info=True)
        with telemetry.span("custom", foo=1):
            telemetry.annotate(bar=2)
    names = [c.name for c in sess.root.children]
    assert "solve" in names and "custom" in names
    sp = sess.root.children[names.index("solve")]
    assert [c.name for c in sp.children] == ["dispatch", "execute"]
    assert sp.attrs["method"] == "cg" and sp.attrs["n"] == 24
    custom = sess.root.children[names.index("custom")]
    assert custom.attrs == {"foo": 1, "bar": 2}
    assert len(sess.solves) == 1
    rec = sess.solves[0]
    assert rec["key"] == "cg/gspmd/ref/n24/float32"
    assert rec["iters_to_tol"] == rec["iterations"]
    assert rec["converged"] is True


def test_session_spans_land_in_the_profiler_trace(tmp_path, rng):
    """An armed session with ``profiler_dir`` writes its spans into the
    profiler's own trace (the file that holds the device ops), nested as
    they ran, and a Perfetto copy of it."""
    a, b = _sys(24, rng, spd=True)
    with telemetry.session("t", profiler_dir=str(tmp_path)):
        api.solve(a, b, method="cg", tol=1e-5)
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert glob.glob(str(tmp_path / "**" / "perfetto_trace.json.gz"),
                     recursive=True)
    spans = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    spans.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.end_ns))
    (s0, s1), = spans["repro.solve"]
    for child in ("repro.dispatch", "repro.execute"):
        (c0, c1), = spans[child]
        assert s0 <= c0 <= c1 <= s1, child
    assert spans["repro.dispatch"][0][1] <= spans["repro.execute"][0][0]


def test_span_disarmed_yields_none():
    with telemetry.span("x") as sp:
        assert sp is None
    telemetry.annotate(anything=1)      # no-op, must not raise


def test_sessions_nest(rng):
    a, b = _sys(24, rng, spd=True)
    with telemetry.session("outer") as so:
        with telemetry.session("inner") as si:
            api.solve(a, b, method="cg", tol=1e-5)
        assert telemetry.active() is so
    assert telemetry.active() is None
    assert [c.name for c in si.root.children] == ["solve"]


def test_attempt_spans_resilient(rng):
    a, b = _sys(24, rng, spd=True)

    def find(sp, name, out):
        if sp.name == name:
            out.append(sp)
        for c in sp.children:
            find(c, name, out)
        return out

    with telemetry.session("t") as sess:
        api.solve(a, b, method="cg", policy="resilient", return_info=True)
    attempts = find(sess.root, "attempt", [])
    assert attempts and attempts[0].attrs["rung"] == 0
    assert attempts[0].attrs["reason"] == "ok"
    # each attempt nests a full solve -> dispatch/execute subtree
    assert find(attempts[0], "dispatch", [])


# --------------------------------------------------------------------------
# communication volume
# --------------------------------------------------------------------------

def test_comm_bytes_lu_panel_bcast(mesh1, rng):
    n, nb = 160, 32
    a, b = _sys(n, rng, spd=False)
    with telemetry.session("t") as sess:
        api.solve(a, b, method="lu", engine="spmd", mesh=mesh1,
                  block_size=nb)
    rows = {e["site"]: e for e in sess.comm.table()}
    assert "lu_panel_bcast" in rows, sorted(rows)
    e = rows["lu_panel_bcast"]
    per_call = n * (nb + 1) * 4          # packed (panel ‖ perm), f32
    # two traced bcasts (pipeline-fill + lookahead in-loop); the in-loop
    # one executes nblocks times
    assert e["calls"] == 2
    assert e["payload_bytes"] == 2 * per_call
    assert e["total_bytes"] == per_call * (1 + n // nb)
    assert rows["trsv_bcast"]["total_bytes"] > 0       # the two solves
    assert sess.comm.total_bytes() >= e["total_bytes"]


def test_comm_site_innermost_wins(mesh1, rng):
    from repro.telemetry import comm as tcomm
    with tcomm.capture() as prof:
        with tcomm.site("outer"):
            with tcomm.site("inner", iters=3):
                tcomm.record("psum", jnp.zeros((4,), jnp.float32))
            tcomm.record("psum", jnp.zeros((2,), jnp.float32))
    rows = {e["site"]: e for e in prof.table()}
    assert rows["inner"]["total_bytes"] == 16 * 3
    assert rows["outer"]["total_bytes"] == 8


# --------------------------------------------------------------------------
# metrics + report
# --------------------------------------------------------------------------

def test_metrics_registry_and_prometheus():
    metrics.reset()
    metrics.counter_inc("solves_total")
    metrics.counter_inc("solves_total", 2)
    metrics.gauge_set("queue_depth", 1.5)
    for v in (0.3, 3.0, 30.0):
        metrics.histogram_observe("latency_ms", v)
    assert metrics.get_counter("solves_total") == 3
    j = metrics.export_json()
    assert j["counters"]["solves_total"] == 3
    assert j["gauges"]["queue_depth"] == 1.5
    h = j["histograms"]["latency_ms"]
    assert h["count"] == 3 and h["p50"] == 3.0
    text = metrics.export_prometheus()
    assert "# TYPE solves_total counter" in text
    assert 'latency_ms_bucket{le="+Inf"} 3' in text
    assert "latency_ms_count 3" in text
    metrics.reset()


def test_span_latency_histograms(rng):
    a, b = _sys(24, rng, spd=True)
    metrics.reset()
    with telemetry.session("t") as sess:
        api.solve(a, b, method="cg", tol=1e-5)
    hists = sess.to_dict()["metrics"]["histograms"]
    assert "span_solve_ms" in hists and "span_dispatch_ms" in hists
    metrics.reset()


def test_report_cli(tmp_path, capsys, rng):
    a, b = _sys(24, rng, spd=True)
    with telemetry.session("t") as sess:
        api.solve(a, b, method="cg", tol=1e-5, return_info=True)
    p = tmp_path / "TELEM_t.json"
    sess.save(str(p))
    assert report.main([str(p)]) == 0
    out = capsys.readouterr().out
    assert "telemetry session" in out
    assert "spans" in out and "cg" in out
