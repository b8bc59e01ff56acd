"""Performance observatory: roofline-attributed solve records, the
once-per-compile analysis contract, machine-profile override, the
zero-overhead-when-disarmed guarantee with perf installed, the
efficiency regression gate, report rendering of old and new TELEM
schemas, and the serve /metrics endpoint + request log."""
import io
import json
import os
import threading
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.core import api
from repro.telemetry import metrics, perf, report


TEST_MACHINE = perf.MachineProfile(
    name="test-rig", platform="cpu", peak_flops=1e11, hbm_bw=5e10,
    link_bw=5e10, source="override")


@pytest.fixture(autouse=True)
def _pinned_machine():
    """Deterministic peaks: no micro-calibration inside the tests."""
    perf.set_machine(TEST_MACHINE)
    yield
    perf.set_machine(None)


def _spd_system(n, seed=0):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((n, n)).astype(np.float32)
    a = (a @ a.T / n + 4 * np.eye(n)).astype(np.float32)
    b = rng.standard_normal(n).astype(np.float32)
    return jnp.asarray(a), jnp.asarray(b)


# --------------------------------------------------------------------------
# per-solve attribution
# --------------------------------------------------------------------------

def test_perf_record_schema():
    """Every eligible solve under session(perf=True) carries the full
    perf sub-record: throughput, roofline, memory, compile time."""
    a, b = _spd_system(64)
    with telemetry.session("t", perf=True) as sess:
        api.solve(a, b, method="cg", tol=1e-6)
        api.solve(a, b, method="lu")
    assert len(sess.solves) == 2
    for rec in sess.solves:
        p = rec["perf"]
        assert p["t_execute_ms"] > 0
        assert p["achieved_gflops"] > 0
        assert p["achieved_hbm_gbs"] > 0
        assert p["machine"] == "test-rig"
        roof = p["roofline"]
        assert roof["bottleneck"] in ("compute", "memory", "collective")
        assert roof["efficiency_pct"] > 0
        assert set(roof) >= {"t_bound_ms", "t_compute_ms", "t_memory_ms",
                             "t_collective_ms"}
        assert p["memory"]["peak_bytes"] > 0
        assert p["memory"]["temp_bytes"] >= 0
    # first solve of each config pays the compile, and it is recorded
    assert sess.solves[0]["perf"]["compile_s"] > 0
    # iterative records carry concrete iteration counts (the AOT path
    # requests return_info inside the executable)
    assert sess.solves[0]["iterations"] > 0
    d = sess.to_dict()
    assert d["machine"]["name"] == "test-rig"
    assert d["perf"]["executables"] == 2


def test_analysis_runs_once_per_compile():
    """The contract the overhead gate enforces in wall time, checked
    structurally: N solves of one configuration = exactly one HLO
    analysis, one compile, compile_s only on the first record."""
    a, b = _spd_system(48)
    with telemetry.session("t", perf=True) as sess:
        for _ in range(4):
            api.solve(a, b, method="cg", tol=1e-6)
    assert sess.perf.analyses == 1
    assert len(sess.perf.executables()) == 1
    assert sess.solves[0]["perf"]["compile_s"] > 0
    assert all(r["perf"]["compile_s"] == 0.0 for r in sess.solves[1:])


def test_iteration_scaling_for_iterative_methods():
    """The while-trip model charges maxiter; attribution scales modeled
    work down to the iterations that ran, so a converged-early CG does
    not report maxiter/iters-times the achieved throughput."""
    a, b = _spd_system(64)
    with telemetry.session("t", perf=True) as sess:
        api.solve(a, b, method="cg", tol=1e-6, maxiter=500)
    rec = sess.solves[0]
    assert 0 < rec["iterations"] < 500
    scale = rec["perf"]["iter_scale"]
    assert scale == pytest.approx(max(rec["iterations"], 1) / 500,
                                  abs=1e-6)
    # direct methods never scale
    with telemetry.session("t2", perf=True) as sess2:
        api.solve(a, b, method="lu")
    assert sess2.solves[0]["perf"]["iter_scale"] == 1.0


def test_return_value_matches_plain_path():
    """The AOT routing is an implementation detail: callers get the
    same x / SolveResult shapes armed or not, and the same answer."""
    a, b = _spd_system(48)
    x_plain = np.asarray(api.solve(a, b, method="cg", tol=1e-8))
    with telemetry.session("t", perf=True):
        x_armed = api.solve(a, b, method="cg", tol=1e-8)
        r_armed = api.solve(a, b, method="cg", tol=1e-8, return_info=True)
    assert x_armed.shape == x_plain.shape
    np.testing.assert_allclose(np.asarray(x_armed), x_plain, atol=1e-4)
    assert hasattr(r_armed, "iterations")


def test_ineligible_solves_still_record():
    """Solves the observatory cannot AOT-route (callable precond) fall
    back to the plain path and still produce a (perf-less) record."""
    a, b = _spd_system(32)
    with telemetry.session("t", perf=True) as sess:
        api.solve(a, b, method="cg", tol=1e-6, precond=lambda r: r)
    assert len(sess.solves) == 1
    assert "perf" not in sess.solves[0]
    assert sess.perf.analyses == 0


def test_disarmed_jaxpr_identical_with_perf_session():
    """perf=True must preserve the telemetry stack's contract: after
    the session closes, traced jaxprs are byte-identical to before
    (fresh closure per trace — jax caches tracing on fn identity)."""
    a, b = _spd_system(32)
    mk = lambda: (lambda A, B: api.solve(A, B, method="cg", tol=1e-6))
    before = str(jax.make_jaxpr(mk())(a, b))
    with telemetry.session("t", perf=True):
        api.solve(a, b, method="cg", tol=1e-6)      # exercise the AOT path
        inside = str(jax.make_jaxpr(mk())(a, b))
    after = str(jax.make_jaxpr(mk())(a, b))
    assert before == after
    # tracers are ineligible: user jits under an armed session trace
    # the same armed graph they would without the observatory
    assert inside != before      # convergence arming, not perf, differs


# --------------------------------------------------------------------------
# machine profiles
# --------------------------------------------------------------------------

def test_machine_profile_detection_and_override():
    perf.set_machine(None)
    m = perf.detect()
    assert m.platform in ("cpu", "gpu", "tpu")
    assert m.peak_flops > 0 and m.hbm_bw > 0 and m.link_bw > 0
    assert m.source in ("table", "calibrated")
    assert perf.detect() is m            # cached, not re-measured
    perf.set_machine(TEST_MACHINE)
    assert perf.detect().name == "test-rig"
    assert TEST_MACHINE.to_dict()["peak_flops"] == 1e11


def test_roofline_uses_detected_peaks():
    """roofline(peaks=...) must divide by the supplied machine, not the
    hard-coded v5e constants."""
    from repro.analysis import hlo, roofline
    cost = hlo.HloCost(flops=1e9, traffic_bytes=1e6)
    slow = perf.MachineProfile("slow", "cpu", 1e9, 1e9, 1e9, "override")
    fast = perf.MachineProfile("fast", "cpu", 1e12, 1e12, 1e12, "override")
    kw = dict(chips=1, model_flops_global=0.0)
    r_slow = roofline.roofline("k", cost, peaks=slow, **kw)
    r_fast = roofline.roofline("k", cost, peaks=fast, **kw)
    assert r_slow.t_compute == pytest.approx(1.0)
    assert r_fast.t_compute == pytest.approx(1e-3)
    r_default = roofline.roofline("k", cost, **kw)
    assert r_default.peak_flops != slow.peak_flops       # v5e default


def test_rank_work_model_imbalance():
    # iterative contiguous rows: n=100 over 3 ranks pads the last rank
    w = perf.rank_work_model(100, 3, direct=False, block_size=32)
    assert len(w) == 3 and w[0] == w[1] > w[2] > 0
    # direct block-cyclic: later panels concentrate on fewer owners,
    # but cycling keeps the spread bounded
    w = perf.rank_work_model(512, 4, direct=True, block_size=64,
                             grid=(2, 2))
    assert len(w) == 4 and max(w) / (sum(w) / 4) < 2.0
    assert perf.rank_work_model(64, 1, direct=False, block_size=32) \
        == (1.0,)


# --------------------------------------------------------------------------
# the regression gates
# --------------------------------------------------------------------------

def _telem_with_eff(path, eff_by_key):
    data = {"section": "solvers", "solves": [
        {"key": k, "perf": {"t_execute_ms": 10.0,
                            "roofline": {"efficiency_pct": e}}}
        for k, effs in eff_by_key.items() for e in effs]}
    with open(path, "w") as f:
        json.dump(data, f)


def test_efficiency_gate_fails_on_degraded_record(tmp_path):
    """The acceptance check: an artificially degraded efficiency (same
    key, median collapsed beyond --eff-factor) must fail the gate, and
    a healthy run must pass."""
    from benchmarks.check_regression import check_roofline_efficiency
    ref, cur = tmp_path / "ref", tmp_path / "cur"
    ref.mkdir(), cur.mkdir()
    _telem_with_eff(ref / "TELEM_solvers.json",
                    {"cg/n256": [30.0, 32.0, 31.0]})
    _telem_with_eff(cur / "TELEM_solvers.json",
                    {"cg/n256": [28.0, 30.0, 29.0]})
    assert check_roofline_efficiency(str(cur), str(ref), factor=3.0) == []
    _telem_with_eff(cur / "TELEM_solvers.json",
                    {"cg/n256": [3.0, 2.0, 4.0]})      # 10x collapse
    violations = check_roofline_efficiency(str(cur), str(ref), factor=3.0)
    assert len(violations) == 1 and "cg/n256" in violations[0]


def test_efficiency_gate_skips_missing_and_tiny(tmp_path):
    """Records without perf, sub-ms records, and keys absent from the
    current run are skipped, never failed — PR 8-era TELEM files gate
    cleanly."""
    from benchmarks.check_regression import check_roofline_efficiency
    ref, cur = tmp_path / "ref", tmp_path / "cur"
    ref.mkdir(), cur.mkdir()
    _telem_with_eff(ref / "TELEM_solvers.json", {"cg/n256": [30.0]})
    with open(cur / "TELEM_solvers.json", "w") as f:
        json.dump({"section": "solvers", "solves": [
            {"key": "cg/n256"},                          # no perf at all
            {"key": "cg/n256", "perf": {
                "t_execute_ms": 0.1,                     # sub-quantum
                "roofline": {"efficiency_pct": 0.001}}}]}, f)
    assert check_roofline_efficiency(str(cur), str(ref)) == []


def test_overhead_gate(tmp_path):
    """Within the contract passes; within noise warns but passes; a
    collapse-class ratio (per-solve analysis work) fails."""
    from benchmarks.check_regression import check_perf_overhead

    def write(ratio):
        with open(tmp_path / "BENCH_solvers.json", "w") as f:
            json.dump({"section": "solvers", "rows": [
                {"name": "perf_overhead_cg_n256_float32", "value": ratio,
                 "unit": "ratio", "note": ""},
                {"name": "cg_n256_float32", "value": 9.9, "unit": "ms",
                 "note": ""}]}, f)

    write(1.02)
    assert check_perf_overhead(str(tmp_path), limit=1.05) == []
    write(1.09)                          # over contract, inside noise
    assert check_perf_overhead(str(tmp_path), limit=1.05) == []
    write(1.60)                          # collapse-class: gate fails
    violations = check_perf_overhead(str(tmp_path), limit=1.05)
    assert len(violations) == 1 and "perf_overhead_cg" in violations[0]


# --------------------------------------------------------------------------
# report rendering: new sections + old-schema round trip
# --------------------------------------------------------------------------

def test_report_renders_perf_sections():
    a, b = _spd_system(64)
    with telemetry.session("t", perf=True) as sess:
        api.solve(a, b, method="cg", tol=1e-6)
    txt = report.render(json.loads(json.dumps(sess.to_dict(),
                                              default=str)))
    assert "machine: test-rig" in txt
    assert "roofline attribution" in txt
    assert "executable memory" in txt
    assert "observatory: 1 executables" in txt


def test_report_round_trips_pr8_schema():
    """A TELEM file captured before the observatory existed (checked-in
    fixture) must render without error and without perf sections."""
    path = os.path.join(os.path.dirname(__file__), "fixtures",
                        "TELEM_solvers_pr8.json")
    with open(path) as f:
        data = json.load(f)
    txt = report.render(data)
    assert "telemetry session 'solvers'" in txt
    assert "-- solves (convergence) --" in txt
    assert "roofline attribution" not in txt
    assert report.main([path]) == 0          # CLI path too


def test_report_tolerates_sparse_dicts():
    """Hand-rolled / truncated session dicts (missing comm fields, no
    metrics) must render, not KeyError."""
    txt = report.render({"section": "x", "comm": [{"kind": "psum"}],
                         "spans": [{"span": "solve"}],
                         "solves": [{"method": "cg"}]})
    assert "psum" in txt


# --------------------------------------------------------------------------
# metrics registry thread safety
# --------------------------------------------------------------------------

def test_metrics_registry_thread_safe():
    """Concurrent mutation + export must neither drop counts nor raise
    (dict-changed-during-iteration) — the /metrics handler exports while
    the batcher mutates."""
    metrics.reset()
    errs = []

    def mutate():
        try:
            for _ in range(500):
                metrics.counter_inc("ts_counter")
                metrics.histogram_observe("ts_hist", 1.0)
        except Exception as e:          # pragma: no cover
            errs.append(e)

    def export():
        try:
            for _ in range(200):
                metrics.export_prometheus()
                metrics.export_json()
        except Exception as e:          # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=mutate) for _ in range(4)] \
        + [threading.Thread(target=export) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert errs == []
    assert metrics.get_counter("ts_counter") == 2000
    assert metrics.get_histogram("ts_hist").n == 2000


# --------------------------------------------------------------------------
# serve: /metrics endpoint + structured request log
# --------------------------------------------------------------------------

def test_serve_metrics_endpoint_and_request_log():
    from repro.serve import ServeClient
    log = io.StringIO()
    client = ServeClient(max_batch=2, max_delay_ms=0.5, metrics_port=0,
                         request_log=log)
    try:
        rng = np.random.default_rng(3)
        n = 24
        a = rng.standard_normal((n, n)).astype(np.float32)
        a = a @ a.T + n * np.eye(n, dtype=np.float32)
        b = rng.standard_normal(n).astype(np.float32)
        client.solve(a, b, method="cg", tol=1e-5)
        port = client.server.metrics_server.port
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as resp:
            assert "version=0.0.4" in resp.headers["Content-Type"]
            body = resp.read().decode()
        assert "# TYPE serve_requests counter" in body
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/stats", timeout=10) as resp:
            stats = json.load(resp)
        assert stats["requests_served"] >= 1
        assert stats["cache"]["compile_s_total"] > 0
        assert any(k.startswith("cg/solve/") for k in
                   stats["cache"]["keys"])
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10) as resp:
            assert resp.read() == b"ok\n"
    finally:
        client.close()
    assert client.server.metrics_server is None      # stopped with server
    recs = [json.loads(line) for line in log.getvalue().splitlines()]
    assert len(recs) == 1
    assert recs[0]["method"] == "cg" and recs[0]["n"] == 24
    assert recs[0]["latency_ms"] > 0 and recs[0]["converged"] is True


def test_cache_records_per_key_compile_seconds():
    from repro.serve import ExecutableCache, make_key
    cache = ExecutableCache()
    key = make_key("cg", 16, "float32", tol=1e-6, maxiter=50)
    fn = cache.get_or_build(key)
    a = jnp.eye(16) * 2.0
    b = jnp.ones((16,))
    fn(a, b)                                   # first call: AOT compile
    fn(a, b)                                   # second: compiled fast path
    s = cache.stats()
    assert s["compile_s_total"] > 0
    (label, info), = s["keys"].items()
    assert label == "cg/solve/n16/float32"
    assert info["compile_s"] > 0 and info["flops"] > 0
    assert cache.key_info[key]["compile_s"] == info["compile_s"]
