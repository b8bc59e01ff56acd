"""Shared blocking / padding policy for the direct (factorization) path.

One rule for lu / cholesky / triangular instead of three ad-hoc
ValueErrors: ``block_size`` is clamped to ``n`` and, when the clamped
block does not divide ``n``, the operands are padded up to the next block
multiple.  Padding is *exact*: the padded system is block-diagonal
``[[A, 0], [0, I]]`` with a zero-padded right-hand side, so the pad rows
factor/solve trivially (unit pivots, zero solution components) and the
leading ``n`` components of the solution are unchanged.  Only genuinely
impossible requests (``block_size < 1``, non-square ``a``) raise.

The padded shapes are static functions of ``(n, block_size)``, so the
``lax.fori_loop`` factorizations built on top stay O(1) in trace/compile
cost regardless of ``n``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

BACKENDS = ("ref", "pallas")


def check_backend(backend: str, mesh=None) -> None:
    """Single validation used by every direct-path entry point."""
    check_backend_name(backend)
    if backend == "pallas" and mesh is not None:
        raise ValueError("backend='pallas' is single-device only on this "
                         "path; drop mesh=, use backend='ref', or use the "
                         "distributed direct path (engine='spmd'), which "
                         "runs the Pallas kernels per-shard")


def check_backend_name(backend: str) -> None:
    """Name-only validation (the spmd direct path allows 'pallas' with a
    mesh — the kernels run on each shard's local blocks)."""
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected {BACKENDS}")


def pallas_float32(dtype) -> bool:
    """Whether a ``backend="pallas"`` request at ``dtype`` runs the f32
    kernels.  Mosaic lowers them for float32 only, so on a TPU any other
    dtype raises rather than quietly running something else.  Off-TPU the
    kernels are interpreted and the caller keeps its exact path for other
    dtypes (float64 keeps f64 accuracy)."""
    if dtype == jnp.float32:
        return True
    if jax.default_backend() == "tpu":
        raise ValueError(f"backend='pallas' kernels are float32-only on "
                         f"TPU; got {jnp.dtype(dtype).name} — cast to "
                         "float32 or use backend='ref'")
    return False


def effective_backend(backend: str, dtype) -> str:
    """The direct path's backend after :func:`pallas_float32`: off-TPU a
    non-f32 ``"pallas"`` request runs the jnp reference path."""
    if backend == "pallas" and not pallas_float32(dtype):
        return "ref"
    return backend


def choose_block(n: int, block_size: int) -> int:
    if block_size < 1:
        raise ValueError(f"block_size={block_size} must be >= 1")
    return min(block_size, n)


def padded_size(n: int, nb: int) -> int:
    return -(-n // nb) * nb


def pad_system(a: jax.Array, block_size: int) -> tuple[jax.Array, int, int]:
    """Return ``(a_padded, nb, n_padded)`` with an identity pad block.

    The identity pad keeps every structure the factorizations need: LU
    pivots in the pad block are exact 1s, SPD-ness is preserved for
    Cholesky, and triangular pads solve trivially.
    """
    n = a.shape[-1]
    if a.ndim != 2 or a.shape[0] != n:
        raise ValueError(f"expected a square (n, n) matrix, got {a.shape}")
    nb = choose_block(n, block_size)
    n_pad = padded_size(n, nb)
    if n_pad != n:
        pad = n_pad - n
        a = jnp.pad(a, ((0, pad), (0, pad)))
        a = a.at[n:, n:].set(jnp.eye(pad, dtype=a.dtype))
    return a, nb, n_pad


def pad_system_spmd(a: jax.Array, block_size: int, nprocs: int
                    ) -> tuple[jax.Array, int, int]:
    """Identity-pad for the block-cyclic distributed path: same policy as
    :func:`pad_system`, but the padded size is a multiple of
    ``nb * nprocs`` so every process owns the same number of block
    columns (ScaLAPACK-style uniform local storage)."""
    n = a.shape[-1]
    if a.ndim != 2 or a.shape[0] != n:
        raise ValueError(f"expected a square (n, n) matrix, got {a.shape}")
    nb = choose_block(n, block_size)
    n_pad = padded_size(n, nb * nprocs)
    if n_pad != n:
        pad = n_pad - n
        a = jnp.pad(a, ((0, pad), (0, pad)))
        a = a.at[n:, n:].set(jnp.eye(pad, dtype=a.dtype))
    return a, nb, n_pad


def pad_rect(a: jax.Array, block_size: int
             ) -> tuple[jax.Array, int, int, int]:
    """Rectangular pad policy for the least-squares (QR) path: pad rows
    and columns *independently* up to block multiples.  Returns
    ``(a_padded, nb, m_padded, n_padded)``.

    The pad is the rectangular generalization of :func:`pad_system`'s
    identity extension: ``[[A, 0], [0, E]]`` with ``E = [I; 0]`` holding
    one unit column per pad column, each on its own pad row (rows are
    padded far enough to host them, so ``m_padded`` may exceed the next
    block multiple of ``m`` when ``n`` needs more pad than ``m``).  The
    padded matrix keeps full column rank, its R factor is block-diagonal
    ``[[R, 0], [0, ±I]]``, and a zero-padded right-hand side solves to
    exact zeros in the pad components — the leading ``n`` solution
    components are unchanged.  Only genuinely impossible requests raise:
    ``block_size < 1``, or an underdetermined ``m < n`` (this path is
    least squares; transpose and use ``matvec_t``-based methods for
    minimum-norm problems).
    """
    if a.ndim != 2:
        raise ValueError(f"expected a 2-D (m, n) matrix, got {a.shape}")
    m, n = a.shape
    if m < n:
        raise ValueError(
            f"underdetermined system {a.shape} (m < n): the QR/LSQR path "
            "solves least squares for m >= n; solve the transposed system "
            "for the minimum-norm solution")
    nb = choose_block(n, block_size)
    n_pad = padded_size(n, nb)
    # rows must gain at least one pad row per pad column (to host E's
    # unit entries); bump by whole blocks until they do
    m_pad = padded_size(m, nb)
    while m_pad - m < n_pad - n:
        m_pad += nb
    if (m_pad, n_pad) != (m, n):
        a = jnp.pad(a, ((0, m_pad - m), (0, n_pad - n)))
        pad_cols = n_pad - n
        if pad_cols:
            a = a.at[m + jnp.arange(pad_cols), n + jnp.arange(pad_cols)] \
                 .set(jnp.ones((pad_cols,), a.dtype))
    return a, nb, m_pad, n_pad


def bucket_ladder(n_max: int = 8192, n_min: int = 16) -> tuple[int, ...]:
    """The serving layer's shape-bucket rungs: powers of two plus their
    3/2 midpoints (16, 24, 32, 48, 64, 96, 128, ...), capped at
    ``n_max``.  Geometric with ratio ≤ 1.5, so bucketing never pads a
    system by more than 50% of its rows while heterogeneous request
    sizes collapse onto O(log n) distinct compiled shapes."""
    if n_min < 2 or n_max < n_min:
        raise ValueError(f"need 2 <= n_min <= n_max, got "
                         f"({n_min}, {n_max})")
    rungs = []
    p = 1
    while p < n_max:
        p *= 2
        for r in (p, p * 3 // 2):
            if n_min <= r <= n_max:
                rungs.append(r)
    if not rungs or rungs[-1] < n_max:
        rungs.append(n_max)
    return tuple(sorted(set(rungs)))


def bucket_size(n: int, ladder: tuple[int, ...] | None = None) -> int:
    """Smallest ladder rung >= n.  Sizes above the top rung fall back to
    the next 128-multiple (still a static shape, just an uncommon one)."""
    if n < 1:
        raise ValueError(f"n={n} must be >= 1")
    for r in (bucket_ladder() if ladder is None else sorted(ladder)):
        if r >= n:
            return r
    return padded_size(n, 128)


def pad_square_to(a: jax.Array, n_pad: int) -> jax.Array:
    """Identity-pad a square system up to an *explicit* target size — the
    same exact ``[[A, 0], [0, I]]`` extension as :func:`pad_system`, but
    to a caller-chosen ``n_pad`` (a bucket rung) rather than the next
    block multiple.  The leading ``n`` solution components are unchanged
    and the pad rows solve to exact zeros against a zero-padded rhs."""
    n = a.shape[-1]
    if a.ndim != 2 or a.shape[0] != n:
        raise ValueError(f"expected a square (n, n) matrix, got {a.shape}")
    if n_pad < n:
        raise ValueError(f"cannot pad {n} rows down to {n_pad}")
    if n_pad == n:
        return a
    pad = n_pad - n
    a = jnp.pad(a, ((0, pad), (0, pad)))
    return a.at[n:, n:].set(jnp.eye(pad, dtype=a.dtype))


def pad_rhs(b: jax.Array, n_padded: int) -> jax.Array:
    """Zero-pad the leading axis of a right-hand side up to ``n_padded``."""
    pad = n_padded - b.shape[0]
    if pad < 0:
        raise ValueError(f"rhs has {b.shape[0]} rows; factor only covers "
                         f"{n_padded}")
    if pad:
        b = jnp.pad(b, ((0, pad),) + ((0, 0),) * (b.ndim - 1))
    return b
