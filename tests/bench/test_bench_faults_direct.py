"""The HPL cells' check, run through the harness on the CPU: sound runs
are correct, and a run whose timed path is broken is not."""
import json
import os
import subprocess
import sys

import jax
import pytest

from bench_cells import ROOT, run_tiny


def test_sound_hpl_run_is_correct():
    out = run_tiny("hpl-n28672.fresh", seconds=0.3)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0


def test_an_answer_altered_where_it_is_produced_is_caught(monkeypatch):
    from repro.core import api
    real = api.solve
    monkeypatch.setattr(api, "solve", lambda a, b, **kw:
                        real(a, b, **kw).at[0].add(0.5))
    out = run_tiny("hpl-n28672.fresh", seconds=0.3)
    assert not out["correct"]
    assert out["checks"]["hpl_ratio"]["value"] > out["checks"][
        "hpl_ratio"]["limit"]


SPMD = """
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
import bench_cells
from repro.core import pblas
if {fault!r} == "no_exchange":
    pblas.psum = lambda x, axes: x
import jax
# the HPL cell's driver on four chips, as a four-chip cell's files give it
entry, workload, config = bench_cells.tiny("hpl-n28672.fresh")
entry = dict(entry, name="hpl-4chip.fresh", chips=4)
config = dict(config, chips=4)
print(json.dumps(bench_cells.run_tiny(
    entry["name"], seconds=0.3, entry=entry, workload=workload,
    config=config, devices=jax.devices()[:4])))
"""


@pytest.mark.parametrize("fault", ["none", "no_exchange"])
def test_four_chip_run_without_its_exchange_is_caught(fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = SPMD.format(root=str(ROOT / "tests" / "bench"),
                       src=str(ROOT / "src"), fault=fault)
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["device"]["count"] == 4
    assert out["correct"] is (fault == "none"), out["checks"]


def test_high_control_runs_through_the_program_switch(capsys):
    """The HPL control: the program's own switch to three bf16 passes.
    On the CPU a float32 product is exact at any setting, so this checks
    that the control runs and reports; its reading comes from the chip."""
    from bench import control
    from bench_cells import tiny
    from repro.core import api
    e, w, c = tiny("hpl-n28672.fresh")
    try:
        control.readings(e, w, c, [5], "high", jax.devices()[:1])
        assert api.MATMUL_PRECISION == "high"
    finally:
        control.set_control(None)
    assert api.MATMUL_PRECISION == "highest"
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["mode"] == "high" and line["checks"]["hpl_ratio"] < 1


def test_control_through_the_harness_prints_its_result_line(capsys):
    """``--harness-seeds``: the cell run by ``bench.run.run_cell`` with the
    control on, its result line tagged and ending with the checks; the
    precision is back at ``highest`` afterwards."""
    from bench import control
    from bench_cells import MANIFEST, tiny
    from repro.core import api
    e, w, c = tiny("hpl-n28672.fresh")
    control.harness(e, w, c, 2**33 + 7, 0.2, "default", MANIFEST,
                    jax.devices()[:1])
    assert api.MATMUL_PRECISION == "highest"
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["control"] == "default" and out["seed"] == 2**33 + 7
    assert list(out)[-1] == "checks" and out["attempted"] >= 1
