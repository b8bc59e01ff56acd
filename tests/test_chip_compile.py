"""Compile the main path's Pallas kernels at real widths for a described
TPU v5e, without a chip attached.

The TPU compiler is installed with jaxlib and compiles for a topology that
is described rather than attached, so these tests catch what interpret
mode cannot: tiles not aligned to the (8, 128) layout, and kernels that
ask for more VMEM than the chip has.  Nothing runs; each test only asserts
that the program compiles and that the kernel survived into it as a
``tpu_custom_call``.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and the test workers all
import this file.  Kernels are called with ``interpret=False`` explicitly
because their ``interpret=None`` default keys on ``jax.default_backend()``,
which is the CPU here.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

N = 16384          # the one-chip dense systems of chip_smoke.py
NB = 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


F32 = jnp.float32


@pytest.mark.parametrize("kind", ["lu", "cholesky"])
def test_panel_update(one_chip, kind):
    from repro.kernels import factor_fused
    update = getattr(factor_fused, f"{kind}_panel_update")
    _compile(lambda a, linv, k: update(a, linv, k, nb=NB, interpret=False),
             one_chip, ((N, N), F32), ((NB, NB), F32), ((), jnp.int32))


def test_gemm_rank_nb(one_chip):
    from repro.kernels import gemm
    _compile(lambda a, b: gemm.matmul(a, b, bm=NB, bn=NB, bk=NB,
                                      interpret=False),
             one_chip, ((N, NB), F32), ((NB, N), F32))


@pytest.mark.parametrize("side", ["lower", "upper"])
def test_trsm(one_chip, side):
    """L streams through VMEM in tiles, so n = 16384 (a 1 GiB factor)
    compiles; the whole-L window it replaced did not."""
    from repro.kernels import trsm
    solve = getattr(trsm, f"trsm_{side}_auto")
    _compile(lambda t, b: solve(t, b, unit_diagonal=False, interpret=False),
             one_chip, ((N, N), F32), ((N,), F32))


@pytest.mark.parametrize("n", [N, 134144])
def test_fused_cg_update(one_chip, n):
    """134144 = 1048 rows of 128 lanes: no multiple of 8 below 256
    divides it except 8, which is what the block must be."""
    from repro.kernels import krylov_fused
    _compile(lambda x, r, p, ap, alpha: krylov_fused.fused_cg_update_auto(
        x, r, p, ap, alpha, interpret=False),
        one_chip, *[((n,), F32)] * 4, ((), F32))


def test_bsr_spmm_stencil(one_chip):
    """A 24³ 7-point stencil in 128-row bricks: 108 block rows, at most
    11 bricks each."""
    from repro.kernels import spmv
    nbr, max_blk = 108, 11
    flat = ((nbr * max_blk,), jnp.int32)
    _compile(lambda data, brick, col, valid, xb: spmv.bsr_spmm(
        data, brick, col, valid, xb, nbr=nbr, interpret=False),
        one_chip, ((nbr * max_blk, NB, NB), F32), flat, flat, flat,
        ((nbr, NB, 1), F32))


def test_lu_row_gather_has_no_fill(one_chip):
    """The pivot gather promises its indices in bounds: no whole-matrix
    select fills out-of-bounds rows after it, and the solve's temporaries
    stay near three matrices (a fill's select would add a fourth)."""
    import re
    from repro.core import api
    args = [jax.ShapeDtypeStruct(s, F32, sharding=one_chip)
            for s in ((N, N), (N,))]
    compiled = jax.jit(lambda a, b: api.solve(
        a, b, method="lu", block_size=NB)).lower(*args).compile()
    fills = [line for line in compiled.as_text().splitlines()
             if re.search(rf"= f32\[{N},{N}\]\S* select\(", line)
             and "lu.pivot" in line]
    assert not fills
    temps = compiled.memory_analysis().temp_size_in_bytes
    assert temps <= 3.1 * N * N * 4


def _lu_solve_compiled(one_chip, n):
    from repro.core import api
    args = [jax.ShapeDtypeStruct(s, F32, sharding=one_chip)
            for s in ((n, n), (n,))]
    return jax.jit(lambda a, b: api.solve(
        a, b, method="lu", block_size=NB)).lower(*args).compile()


def _stage_loops(text):
    """``{window: [body instruction lines]}`` for each ``while`` of the
    compiled module that carries a square f32 matrix (its window)."""
    import re
    comps, cur = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif cur is not None:
            cur.append(line)
    loops = {}
    for line in text.splitlines():
        if " while(" not in line:
            continue
        window = re.search(r"= \(.*?f32\[(\d+),\1\]", line)
        if window:
            body = re.search(r"body=%?([\w.\-]+)", line).group(1)
            loops.setdefault(int(window.group(1)), []).extend(comps[body])
    return loops


def test_lu_stages_run_on_their_windows(one_chip):
    """Past the first stage each loop of the one-chip LU works on its own
    trailing window: the rank-nb update's product is there at every
    window of ``lu.stage_bounds``, and no loop after the first holds a
    whole-matrix layout copy (the panel slice's copy shrinks with the
    window)."""
    import re
    from repro.core import lu
    text = _lu_solve_compiled(one_chip, N).as_text()
    windows = {N - s0 * NB for s0 in lu.stage_bounds(N // NB)[:-1]}
    assert len(windows) == 16
    products = {int(m.group(1)) for line in text.splitlines()
                if "lu.update" in line
                for m in [re.search(r"= f32\[(\d+),\1\]\S* "
                                    r"(?:convolution|dot)\(", line)] if m}
    assert products == windows
    loops = _stage_loops(text)
    assert windows <= set(loops)
    whole = re.compile(rf"= f32\[{N},{N}\]\S* copy\(")
    for window, body in loops.items():
        if window < N:
            assert not any(whole.search(line) for line in body), window


def test_lu_at_32768_fits_one_chip(one_chip):
    """The staged windows hold no more than the whole-matrix loop: at
    n = 32768 the solve still compiles for one chip with temporaries
    near three matrices (12.016 GiB before the stages)."""
    compiled = _lu_solve_compiled(one_chip, 32768)
    temps = compiled.memory_analysis().temp_size_in_bytes
    assert temps <= 3.1 * 32768 * 32768 * 4


def test_spmd_hpl_solve_fits_a_2x2_host(topo):
    """The four-chip HPL cell's program: the spmd LU at n = 49152,
    nb = 128 on a (2, 2) ``solver_mesh``, from the generator's 2-D
    blocks, fits a v5e chip's 15.75 GiB (9.00 GiB of arguments and
    temporaries when the cell was added), and the collectives of its
    change into the cyclic layout carry the ``lu.distribute`` scope."""
    import re
    from jax.sharding import NamedSharding
    from repro.core import api, dist
    from repro.launch.mesh import solver_mesh
    n = 49152
    mesh = solver_mesh(topo.devices)
    a = jax.ShapeDtypeStruct((n, n), F32, sharding=NamedSharding(
        mesh, dist.matrix_spec(mesh)))
    b = jax.ShapeDtypeStruct((n,), F32, sharding=NamedSharding(
        mesh, dist.vector_spec(mesh)))
    compiled = jax.jit(lambda a, b: api.solve(
        a, b, method="lu", block_size=NB, mesh=mesh,
        engine="spmd")).lower(a, b).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        <= 15.75 * 2**30
    layout = [line for line in compiled.as_text().splitlines()
              if re.search(r"= \S+ (all-gather|all-to-all|"
                           r"collective-permute-start)\(", line)]
    assert len(layout) >= 3
    assert all("lu.distribute/" in line for line in layout), layout
