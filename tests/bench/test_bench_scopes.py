"""The direct engine's device scopes: the compiled program names each part
of the LU step, and the trace's ops are sorted by those names into
seconds per solve."""
import contextlib
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from bench_cells import ROOT, run, tiny
from bench import scopes, tracing

_OPCODE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*? ([a-z][\w\-]*)\(")


def opcodes_by_scope(hlo_text):
    """``{scope: {opcode, ...}}`` over every instruction of the text."""
    names = scopes.op_scopes(hlo_text)
    out = {}
    for line in hlo_text.splitlines():
        m = _OPCODE.match(line)
        if m:
            out.setdefault(names[m.group(1)], set()).add(m.group(2))
    return out


def test_op_scopes_takes_the_innermost_lu_scope():
    text = "\n".join([
        'ENTRY %main {',
        '  %fusion.53 = f32[8,8]{1,0} fusion(%p), kind=kLoop, '
        'metadata={op_name="jit(f)/while/body/lu.update/lu.panel/mul"}',
        '  ROOT %gather.2 = f32[8,8]{1,0} gather(%a, %i), '
        'metadata={op_name="jit(f)/while/body/lu.pivot/jit(_take)/gather" '
        'stack_frame_id=5}',
        '  %copy.6 = f32[8,8]{1,0} copy(%a)',
        '  %while.1 = (s32[]) while(%t), metadata={op_name="jit(f)/while"}',
        '}'])
    assert scopes.op_scopes(text) == {"fusion.53": "lu.panel",
                                      "gather.2": "lu.pivot",
                                      "copy.6": None, "while.1": None}


def test_dense_lu_names_each_part_of_the_step():
    n = 512
    a = jax.ShapeDtypeStruct((n, n), jnp.float32)
    b = jax.ShapeDtypeStruct((n,), jnp.float32)
    from repro.core import api
    text = jax.jit(lambda a, b: api.solve(a, b, method="lu",
                                          block_size=128)).lower(
        a, b).compile().as_text()
    ops = opcodes_by_scope(text)
    assert "gather" in ops["lu.pivot"]                 # the row gather
    assert {"dot", "convolution"} & ops["lu.update"]   # rank-nb update
    assert "while" in ops["lu.panel"]                  # the nb-step loop
    for sub in ("lu.fsub", "lu.bsub"):                 # blocked loops
        assert "while" in ops[sub]
        assert {"triangular-solve", "custom-call"} & ops[sub]
    # the block-step loop itself is no part's: its own time is unscoped
    assert "while" in ops[None]
    assert "lu.bcast" not in ops


SPMD = """
import json, re, sys
sys.path[:0] = [{root!r}, {tests!r}, {src!r}]
import jax, jax.numpy as jnp
from repro.core import api
from repro.launch.mesh import solver_mesh
import test_bench_scopes as t
mesh = solver_mesh(jax.devices()[:4])
n = 512
a = jax.ShapeDtypeStruct((n, n), jnp.float32)
b = jax.ShapeDtypeStruct((n,), jnp.float32)
text = jax.jit(lambda a, b: api.solve(a, b, method="lu", block_size=64,
                                      mesh=mesh, engine="spmd")).lower(
    a, b).compile().as_text()
print(json.dumps({{str(k): sorted(v)
                  for k, v in t.opcodes_by_scope(text).items()}}))
"""


def test_spmd_lu_names_the_same_parts_and_the_panel_broadcast():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = SPMD.format(root=str(ROOT), tests=str(ROOT / "tests" / "bench"),
                       src=str(ROOT / "src"))
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    ops = {k: set(v) for k, v in json.loads(
        proc.stdout.strip().splitlines()[-1]).items()}
    assert "all-reduce" in ops["lu.bcast"]             # masked-psum bcast
    assert "gather" in ops["lu.pivot"]
    assert "dot" in ops["lu.update"]
    assert {"conditional", "while"} <= ops["lu.panel"]  # owner-only loop
    for sub in ("lu.fsub", "lu.bsub"):
        assert "while" in ops[sub]


# ---------------------------------------------------------------------------
# seconds per solve from a shaped timeline
# ---------------------------------------------------------------------------

MAP = {"while.1": None, "fusion.1": "lu.pivot", "fusion.2": "lu.update",
       "while.2": "lu.panel", "fusion.3": "lu.panel", "dot.1": "lu.fsub",
       "fusion.9": "lu.update"}


def _run(t, pivot_end=40):
    """One run of the solve at ``t``, 100 ns long, every op inside it."""
    return [(t, t + 90, "while.1"), (t + 10, t + pivot_end, "fusion.1"),
            (t + pivot_end, t + 70, "fusion.2"),
            (t + 70, t + 80, "copy.6"),          # not in the map
            (t + 80, t + 88, "while.2"), (t + 81, t + 85, "fusion.3"),
            (t + 90, t + 100, "dot.1")]


def _device(i, pivot_end):
    ops = (_run(0, pivot_end) + [(110, 140, "fusion.9")]
           + _run(200, pivot_end) + [(350, 450, "fusion.1")])
    mods = [(0, 100, "jit_hpl_solve(7)"), (100, 150, "jit_gen(3)"),
            (200, 300, "jit_hpl_solve(7)"), (350, 450, "jit_hpl_solve(7)")]
    return tracing._device(f"/device:TPU:{i}",
                           [(s, e, f"%{n} = f32[] op()") for s, e, n in ops],
                           mods)


def _trace():
    # the last run leaves the window; the generator's ops are another
    # program's; the second device spends 20 ns more in the gather
    return tracing.Trace([_device(0, 40), _device(1, 60)], [], 0.0, 400.0)


def test_scope_s_partitions_the_programs_op_time_per_solve():
    tr = _trace()
    got = {s: scopes.scope_s(tr, MAP, "hpl_solve", s)
           for s in ("lu.pivot", "lu.update", "lu.panel", "lu.fsub",
                     "lu.bsub", None)}
    ns = 1e-9
    assert got["lu.pivot"] == pytest.approx((30 + 50) / 2 * ns)
    assert got["lu.update"] == pytest.approx((30 + 10) / 2 * ns)
    assert got["lu.panel"] == pytest.approx(8 * ns)    # while + its body
    assert got["lu.fsub"] == pytest.approx(10 * ns)
    assert got["lu.bsub"] == 0.0
    # the loop's own 12 ns and the copy missing from the map
    assert got[None] == pytest.approx(22 * ns)
    # the parts sum to the op-covered time of a run: all of its 100 ns
    assert sum(got.values()) == pytest.approx(100 * ns)


def test_scope_s_reads_nothing_without_a_map_scopes_or_runs():
    tr = _trace()
    assert scopes.scope_s(tr, None, "hpl_solve", "lu.pivot") is None
    assert scopes.scope_s(tr, {}, "hpl_solve", None) is None
    # a program from before the scopes: every instruction unscoped
    bare = dict.fromkeys(MAP)
    assert scopes.scope_s(tr, bare, "hpl_solve", None) is None
    assert scopes.scope_s(tr, MAP, "other_program", "lu.pivot") is None
    late = tracing.Trace(tr.devices, [], 340.0, 400.0)
    assert scopes.scope_s(late, MAP, "hpl_solve", "lu.pivot") is None


READERS = {"direct.panel_s": ["lu.panel"], "direct.pivot_s": ["lu.pivot"],
           "direct.update_s": ["lu.update"],
           "direct.substitution_s": ["lu.fsub", "lu.bsub"],
           "direct.unscoped_s": [None]}


def test_readers_take_the_map_from_the_cells_program_prepared_again():
    """On the CPU: the map comes from the HPL cell's own program, compiled
    again after the window and freed; each reader sums its scopes."""
    entry, workload, config = tiny("hpl-n28672.fresh")
    cell = run.Cell(entry["name"], config, workload, 1, 2**33 + 5,
                    jax.devices()[:1])
    cell.readings["program"] = "hpl_solve"
    names = scopes.program_scopes(cell)
    assert cell.readings["op_scopes"] is names and "solve" not in cell.state
    # one op of the trace for each scope, in one run of the program
    pick = {}
    for instr, scope in names.items():
        pick.setdefault(scope, instr)
    assert {"lu.panel", "lu.pivot", "lu.update", "lu.fsub", "lu.bsub",
            None} <= set(pick)
    order = ["lu.panel", "lu.pivot", "lu.update", "lu.fsub", "lu.bsub",
             None]
    ops = [(10 * i, 10 * i + 10, f"%{pick[s]} = f32[] op()")
           for i, s in enumerate(order)]
    dev = tracing._device("/device:TPU:0", ops,
                          [(0, 60, "jit_hpl_solve(1)")])
    tr = tracing.Trace([dev], [], 0.0, 100.0)
    for name, parts in READERS.items():
        assert run.load_reader(name)(cell, tr) == pytest.approx(
            10e-9 * len(parts)), name
    # a trace with no run of the program reads nothing
    idle = tracing.Trace([tracing._device("/device:TPU:0", [], [])], [],
                         0.0, 100.0)
    assert all(run.load_reader(name)(cell, idle) is None
               for name in READERS)


def test_a_program_cached_before_its_scopes_is_mapped_from_a_fresh_compile(
        tmp_path, monkeypatch):
    """JAX's persistent cache keys a program without its metadata, so the
    executable a run loads may come from a source without the scopes; the
    map then comes from this source, compiled with the cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    entry, workload, config = tiny("hpl-n28672.fresh")
    cell = run.Cell(entry["name"], config, workload, 1, 2**33 + 5,
                    jax.devices()[:1])
    cell.readings["program"] = "hpl_solve"
    driver = run.load_driver(workload["driver"])
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_enable_compilation_cache")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        for k, v in zip(keys, (str(tmp_path), 0, 0, True)):
            jax.config.update(k, v)
        compilation_cache.reset_cache()
        with monkeypatch.context() as m:       # the source before scopes
            m.setattr(jax, "named_scope",
                      lambda name: contextlib.nullcontext())
            bare = scopes._program_text(cell, driver)
        cached = scopes._program_text(cell, driver)
        assert not any(scopes.op_scopes(cached).values())
        names = scopes.program_scopes(cell)
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    assert scopes.instructions(bare) == scopes.instructions(cached)
    assert {"lu.panel", "lu.pivot", "lu.update", "lu.fsub",
            "lu.bsub"} <= set(names.values())
    assert set(names) == set(scopes.op_scopes(cached))
