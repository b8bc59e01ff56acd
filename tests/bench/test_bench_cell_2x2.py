"""The four-chip HPL cell, ``hpl-n49152-2x2.fresh``: its readers of the
panel broadcast, the layout change and the exposed collectives, on
shaped timelines and on the op map of its own program compiled for four
CPU devices, and the cell run through the harness on four CPU devices."""
import json
import os
import subprocess
import sys

import jax
import pytest

from bench_cells import ROOT, run
from bench import collectives, tracing

CELL = "hpl-n49152-2x2.fresh"
PROGRAM = "hpl_solve"


def _device(ops, mods=((0, 1000, "jit_hpl_solve(1)"),), i=0):
    return tracing._device(f"/device:TPU:{i}",
                           [(s, e, f"%{n} = f32[] op()") for s, e, n in ops],
                           list(mods))


CODES = {"psum.63": "all-reduce", "fusion.2": "fusion", "copy.6": "copy",
         "all-gather.12": "all-gather"}


@pytest.mark.parametrize("shape,share", [
    # a collective alone: all of its 200 ns are exposed
    ([(0, 200, "psum.63"), (200, 1000, "fusion.2")], 20.0),
    # under compute: only the 100 ns outside the fusion are exposed
    ([(0, 200, "psum.63"), (50, 150, "fusion.2"), (200, 1000, "copy.6")],
     10.0),
    # no collective at all
    ([(0, 400, "fusion.2"), (400, 1000, "copy.6")], 0.0),
])
def test_exposed_share_on_shaped_timelines(shape, share):
    """``psum.63`` is an all-reduce only by its opcode: the trace names it
    by its instruction, which the reducer alone would not know."""
    tr = tracing.Trace([_device(shape, i=0), _device(shape, i=1)], [],
                       0.0, 2000.0)
    assert collectives.exposed_share(tr, PROGRAM, CODES) == pytest.approx(
        share)
    assert tracing.exposed_collective_s(tr, tr.devices[0]) == 0.0


def test_exposed_share_counts_only_the_programs_runs():
    """A collective of another program (or outside the window's runs)
    counts in neither part of the share; no run reads nothing."""
    dev = _device([(0, 500, "fusion.2"), (500, 1000, "copy.6"),
                   (1100, 1300, "all-gather.12")],
                  mods=[(0, 1000, "jit_hpl_solve(1)"),
                        (1100, 1300, "jit_gen(2)")])
    tr = tracing.Trace([dev], [], 0.0, 2000.0)
    assert collectives.exposed_share(tr, PROGRAM, CODES) == 0.0
    assert collectives.exposed_share(tr, "other", CODES) is None


def test_opcodes_of_a_compiled_text():
    text = "\n".join([
        "ENTRY %main {",
        "  %psum.63 = f32[8,9]{0,1} all-reduce(%x), channel_id=1, "
        'metadata={op_name="jit(f)/shard_map/lu.bcast/psum"}',
        "  ROOT %fusion.2 = f32[8,8]{1,0} fusion(%a), kind=kLoop",
        "}"])
    assert collectives.opcodes(text) == {"psum.63": "all-reduce",
                                         "fusion.2": "fusion"}


# ---------------------------------------------------------------------------
# on four CPU devices: the cell's program and the cell through the harness
# ---------------------------------------------------------------------------

FOUR = """
import json, sys
sys.path[:0] = [{tests!r}, {root!r}, {src!r}]
import jax
import bench_cells
from bench import collectives, peaks, run, scopes, tracing

NAME = {cell!r}
devices = jax.devices()[:4]
entry, workload, config = bench_cells.tiny(NAME)
driver = run.load_driver(workload["driver"])


def program_text(config):
    cell = run.Cell(NAME, config, workload, 4, 0, devices)
    return scopes._program_text(cell, driver)


out = {{}}
# the program at n = 512, a size the cyclic layout takes without padding
text = program_text(dict(config, n=512))
out["scopes_512"] = scopes.op_scopes(text)
out["codes_512"] = collectives.opcodes(text)

# the cell's limit is set from chip readings at n = 49152 (PERF.md); the
# ratio divides by n while the rounding error grows more slowly, and at
# n = 256 it reads about 0.004 on the CPU: held to the one-chip cell's 0.05
workload = dict(workload, limits={{"hpl_ratio": 0.05}})
out["plain"] = bench_cells.run_tiny(NAME, seconds=0.3, devices=devices,
                                    workload=workload)

# the traced run: the CPU's trace has no device plane, so one run of the
# solve is laid on each of four device planes, 1000 ns long, made of the
# cell's own instructions (those the readers will map), one per part
text = program_text(config)
names, codes = scopes.op_scopes(text), collectives.opcodes(text)
COLL = ("all-gather", "all-to-all", "collective-permute", "all-reduce")


def pick(scope, coll):
    return next(i for i, s in names.items()
                if s == scope and (codes[i] in COLL) == coll)


PARTS = [(0, 100, pick("lu.distribute", True)),
         (100, 300, pick("lu.bcast", True)),
         (150, 250, pick("lu.update", False)),    # under the broadcast
         (300, 400, pick("lu.panel", False)),
         (400, 500, pick("lu.pivot", False)),
         (500, 600, pick("lu.fsub", False)),
         (600, 700, pick("lu.bsub", False)),
         (700, 1000, pick(None, False))]
real_load = tracing.load


def load(path):
    tr = real_load(path)
    t0 = tr.t0 + 1000
    tr.devices = [tracing._device(
        f"/device:TPU:{{i}}",
        [(t0 + s, t0 + e, f"%{{n}} = f32[] op()") for s, e, n in PARTS],
        [(t0, t0 + 1000, "jit_hpl_solve(1)")]) for i in range(4)]
    return tr


tracing.load = load
peaks.PEAKS["cpu"] = peaks.PEAKS["TPU v5e"]    # the planes stand for v5e
out["traced"] = bench_cells.run_tiny(NAME, seconds=0.3, trace=True,
                                     devices=devices, workload=workload)
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def four():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = FOUR.format(tests=str(ROOT / "tests" / "bench"), root=str(ROOT),
                       src=str(ROOT / "src"), cell=CELL)
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


LAYOUT = ("all-gather", "all-to-all", "collective-permute")


def test_the_layout_change_collectives_are_scoped_lu_distribute(four):
    names, codes = four["scopes_512"], four["codes_512"]
    layout = {i: names[i] for i, c in codes.items() if c in LAYOUT}
    assert {codes[i] for i in layout} == set(LAYOUT)
    assert set(layout.values()) == {"lu.distribute"}, layout
    bcast = {codes[i] for i, s in names.items() if s == "lu.bcast"}
    assert "all-reduce" in bcast


def _cell(op_scopes):
    cell = run.Cell(CELL, {}, {"driver": "batch_solve"}, 4, 0,
                    jax.devices()[:1])
    cell.readings.update(program=PROGRAM, op_scopes=op_scopes)
    return cell


def _one_of(names, codes, scope, opcodes):
    return next(i for i, s in names.items()
                if s == scope and codes[i] in opcodes)


def test_bcast_and_distribute_read_from_the_compiled_map(four):
    """Two runs on each of two devices: per solve, the broadcast's
    all-reduce and the layout change's all-gather, by self time."""
    names, codes = four["scopes_512"], four["codes_512"]
    gather = _one_of(names, codes, "lu.distribute", {"all-gather"})
    psum = _one_of(names, codes, "lu.bcast", {"all-reduce"})
    ops = [(0, 100, gather), (100, 130, psum), (500, 600, gather),
           (600, 650, psum)]
    mods = [(0, 400, "jit_hpl_solve(1)"), (500, 900, "jit_hpl_solve(1)")]
    tr = tracing.Trace([_device(ops, mods, 0), _device(ops, mods, 1)], [],
                       0.0, 1000.0)
    cell = _cell(names)
    assert run.load_reader("direct.distribute_s")(cell, tr) == \
        pytest.approx(100e-9)
    assert run.load_reader("direct.bcast_s")(cell, tr) == pytest.approx(
        40e-9)


def test_a_program_without_the_layout_scope_reads_zero(four):
    """The parent's program has the ``lu.*`` scopes but no
    ``lu.distribute``: its layout change is unscoped, so the reader reads
    0.0 and the unscoped part holds it; a program with no scope at all
    reads nothing."""
    names, codes = four["scopes_512"], four["codes_512"]
    gather = _one_of(names, codes, "lu.distribute", {"all-gather"})
    parent = {i: (None if s == "lu.distribute" else s)
              for i, s in names.items()}
    tr = tracing.Trace([_device([(0, 100, gather)])], [], 0.0, 2000.0)
    distribute = run.load_reader("direct.distribute_s")
    assert distribute(_cell(parent), tr) == 0.0
    assert run.load_reader("direct.unscoped_s")(_cell(parent), tr) == \
        pytest.approx(100e-9)
    assert distribute(_cell(dict.fromkeys(names)), tr) is None


def test_the_cell_runs_correct_on_four_devices(four):
    out = four["plain"]
    assert out["correct"], out["checks"]
    assert out["device"]["count"] == 4 and out["attempted"] >= 1
    assert set(out["metrics"]) == {"setup_s", "solve_s"}


def test_the_traced_cell_reports_every_per_layer_metric(four):
    out = four["traced"]
    assert out["correct"], out["checks"]
    expected = {m["name"] for m in run.metrics_for(
        run.load_manifest(), CELL, "per_layer")}
    assert len(expected) == 10 and set(out["metrics"]) == expected
    value = {k: v["value"] for k, v in out["metrics"].items()}
    ns = 1e-9
    assert value["direct.distribute_s"] == pytest.approx(100 * ns)
    assert value["direct.bcast_s"] == pytest.approx(100 * ns)
    assert value["direct.update_s"] == pytest.approx(100 * ns)
    # the broadcast runs alone for 100 of its 200 ns, the layout's
    # collective for all of its 100: 200 of the run's 1000 ns
    assert value["collective.exposed_share"] == pytest.approx(20.0)
    # the seven parts partition the run's op-covered 1000 ns
    parts = ("direct.panel_s", "direct.pivot_s", "direct.update_s",
             "direct.substitution_s", "direct.unscoped_s",
             "direct.bcast_s", "direct.distribute_s")
    assert sum(value[p] for p in parts) == pytest.approx(1000 * ns)
