"""The library's contact points with the platform it runs on: kernel tile
choice, TPU peak lookup, compile-cache placement, the float32-only Pallas
rule, and the chip smoke script's refusal to run without a TPU."""
import os
import pathlib
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro import compile_cache
from repro.core import blocking
from repro.kernels.krylov_fused import _pick_block_rows
from repro.telemetry import perf

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("rows,block_rows", [
    (1048, 256), (128, 256), (2048, 256), (6, 2), (1, 256), (96, 40),
    (8 * 131 * 3, 256)])
def test_pick_block_rows_aligned(rows, block_rows):
    br = _pick_block_rows(rows, block_rows)
    assert rows % br == 0
    assert br == rows or (br % 8 == 0 and br <= block_rows)


def test_pick_block_rows_awkward_count():
    """1048 = 8 · 131: the only aligned divisor under 256 is 8 (a count-down
    to any divisor picked 131, which Mosaic refuses)."""
    assert _pick_block_rows(1048, 256) == 8


class _FakeDevice:
    platform = "tpu"

    def __init__(self, kind):
        self.device_kind = kind


@pytest.fixture
def fake_tpu(monkeypatch):
    def install(kind):
        monkeypatch.setattr(jax, "devices", lambda *a: [_FakeDevice(kind)])
    yield install
    perf.set_machine(None)


def test_detect_raises_on_unknown_tpu_kind(fake_tpu):
    fake_tpu("TPU v99 imaginary")
    with pytest.raises(ValueError, match="TPU v99 imaginary"):
        perf.detect(force=True)


def test_detect_reads_v5e_peaks_by_device_kind(fake_tpu):
    fake_tpu("TPU v5 lite")
    m = perf.detect(force=True)
    assert (m.platform, m.source) == ("tpu", "table")
    assert m.peak_flops == 197e12 and m.hbm_bw == 819e9


@pytest.fixture
def cache_dir_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_env_var_is_left_to_jax(monkeypatch, cache_dir_config,
                                              tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(ROOT / ".jax_cache")
    assert compile_cache.enable() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_pallas_non_f32_raises_on_tpu(monkeypatch):
    assert blocking.pallas_float32(jnp.float32)
    assert not blocking.pallas_float32(jnp.float64)     # CPU: exact path
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="float32-only"):
        blocking.pallas_float32(jnp.float64)
    with pytest.raises(ValueError, match="float32-only"):
        blocking.effective_backend("pallas", jnp.bfloat16)
    assert blocking.effective_backend("ref", jnp.float64) == "ref"


def _dot_precisions(jaxpr) -> set[str]:
    return set(re.findall(r"precision=\(?([\w.]+)", str(jaxpr)))


@pytest.mark.parametrize("entry", ["solve_lu", "solve_cg", "factorize",
                                   "executable_factor"])
def test_solves_trace_at_highest_precision(entry):
    """Every product of a solve is traced at Precision.HIGHEST (at the
    TPU's default an f32 product is one bf16 pass); code outside the
    library keeps the default."""
    from repro.core import api
    a = jnp.eye(64) * 4.0 + 0.01
    b = jnp.ones(64)
    traced = {
        "solve_lu": lambda: jax.make_jaxpr(
            lambda a, b: api.solve(a, b, method="lu"))(a, b),
        "solve_cg": lambda: jax.make_jaxpr(
            lambda a, b: api.solve(a, b, method="cg"))(a, b),
        "factorize": lambda: jax.make_jaxpr(
            lambda a, b: api.factorize(a, method="lu")(b))(a, b),
        "executable_factor": lambda: api.make_executable(
            method="lu", mode="factor").trace(a).jaxpr,
    }[entry]()
    assert _dot_precisions(traced) == {"Precision.HIGHEST"}
    assert not _dot_precisions(jax.make_jaxpr(lambda a, b: a @ b)(a, b))


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert proc.stdout == ""            # no phase line, no result line
    assert "needs a TPU" in proc.stderr
