"""Shared benchmark utilities: timing, CSV emission, system builders."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax
import numpy as np

ROWS: list[tuple] = []

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def run_child(code: str, ndev: int, timeout: float = 900) -> dict:
    """Run one device-count sweep point in a fresh CPU process and return
    its ``RESULT {json}`` line.  XLA fixes the device count at start-up,
    so every count needs its own process; ``JAX_PLATFORMS=cpu`` keeps the
    child off any accelerator the parent process holds (these rows are
    CPU emulation).  A child that prints no result fails the section."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={ndev}")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")]
    if not line:
        raise RuntimeError(f"{ndev}-device child exited {proc.returncode}"
                           f" with no result:\n{proc.stderr[-2000:]}")
    return json.loads(line[0][len("RESULT "):])


def emit(bench: str, name: str, value, unit: str, note: str = ""):
    ROWS.append((bench, name, value, unit, note))
    print(f"{bench},{name},{value},{unit},{note}", flush=True)


def timeit(fn, *args, warmup: int = 1, iters: int = 3) -> float:
    """Median wall seconds of fn(*args) with block_until_ready."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def make_system(n: int, *, spd: bool, dtype=np.float32, seed: int = 0):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((n, n)).astype(dtype)
    if spd:
        a = (a @ a.T / n + 4.0 * np.eye(n)).astype(dtype)
    else:
        a = (a + n * np.eye(n)).astype(dtype)
    b = rng.standard_normal(n).astype(dtype)
    return a, b
