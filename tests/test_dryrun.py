"""Production-mesh dry-run smoke: the solver cell lowers and compiles on
512 virtual devices in a subprocess and writes its record where the caller
says."""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


@pytest.fixture(scope="module")
def solver_cell(tmp_path_factory):
    """One dry-run of the CG solver cell on the pod mesh: its process and
    the directory it wrote its record to."""
    out = tmp_path_factory.mktemp("dryrun")
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)      # dryrun.py sets its own device count
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--solver",
         "--solver-method", "cg", "--mesh", "pod", "--out", str(out)],
        capture_output=True, text=True, env=env, timeout=840)
    return proc, out


@pytest.mark.timeout(900)
def test_dryrun_solver_cell(solver_cell):
    proc, _ = solver_cell
    assert "bottleneck=" in proc.stdout, proc.stdout[-2000:] \
        + proc.stderr[-2000:]


@pytest.mark.timeout(900)
def test_artifacts_have_roofline_fields(solver_cell):
    _, out = solver_cell
    assert sorted(os.listdir(out)) == ["solver-cg__n61440__pod.json"]
    with open(os.path.join(out, "solver-cg__n61440__pod.json")) as f:
        r = json.load(f)
    assert (r["method"], r["mesh"], r["chips"]) == ("cg", "pod", 256)
    rl = r["roofline"]
    assert {"t_compute_s", "t_memory_s", "t_collective_s",
            "bottleneck"} <= set(rl)
    assert rl["bottleneck"] in ("compute", "memory", "collective")
