"""Pallas TPU kernels for Muon's Newton–Schulz orthogonalization.

Two paths:

  * ``ns_fused`` — the whole matrix resides in VMEM; all quintic iterations
    run inside one kernel (zero HBM round-trips between iterations).  Its
    VMEM use is bounded by ``fused_vmem_bytes`` and the kernel asks the
    compiler for exactly that much (``FUSED_VMEM_LIMIT`` at most), so
    ``ops.fits_fused`` and the compiler agree on which matrices it takes.
    The inner dots hit the MXU; n and m are padded to multiples of 128 by
    the caller.

  * ``matmul`` — classic tiled (bm×bk)·(bk×bn) matmul with an f32 VMEM
    accumulator, used to compose NS iterations for matrices too large to
    fuse (e.g. GPT-2's 768×3072 MLP and 50304×768 embedding).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.newton_schulz.ref import NS_COEFFS

# VMEM the fused kernel may claim: half of a v5e core's 128 MiB, leaving the
# rest to the compiler's own buffers.
FUSED_VMEM_LIMIT = 64 * 2**20


def fused_vmem_bytes(n: int, m: int) -> int:
    """Upper bound on the fused kernel's VMEM for an (n, m) f32 matrix:
    double-buffered input and output blocks, the iterate and two (n, m)
    temporaries, and the Gram matrix with two (n, n) temporaries."""
    return 4 * (7 * n * m + 3 * n * n)


# ---------------------------------------------------------------------------
# Fused small-matrix NS
# ---------------------------------------------------------------------------

def _ns_fused_body(x_ref, o_ref, *, steps: int, eps: float):
    a, b, c = NS_COEFFS
    x = x_ref[...].astype(jnp.float32)
    norm = jnp.sqrt(jnp.sum(x * x)) + eps
    x = x / norm

    def one(_, x):
        gram = jax.lax.dot_general(x, x, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        poly = b * gram + c * jnp.dot(gram, gram,
                                      preferred_element_type=jnp.float32)
        return a * x + jnp.dot(poly, x, preferred_element_type=jnp.float32)

    x = jax.lax.fori_loop(0, steps, one, x)
    o_ref[...] = x.astype(o_ref.dtype)


def ns_fused(x: jax.Array, steps: int = 5, eps: float = 1e-7,
             interpret: bool = False) -> jax.Array:
    """x: (n, m) with n <= m, both multiples of 128; whole-matrix VMEM
    kernel."""
    n, m = x.shape
    return pl.pallas_call(
        functools.partial(_ns_fused_body, steps=steps, eps=eps),
        out_shape=jax.ShapeDtypeStruct((n, m), x.dtype),
        in_specs=[pl.BlockSpec((n, m), lambda: (0, 0))],
        out_specs=pl.BlockSpec((n, m), lambda: (0, 0)),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=min(FUSED_VMEM_LIMIT,
                                 max(fused_vmem_bytes(n, m), 16 * 2**20))),
        interpret=interpret,
        name="newton_schulz_fused",
    )(x)


# ---------------------------------------------------------------------------
# Tiled matmul (building block for the large-matrix NS path)
# ---------------------------------------------------------------------------

def _matmul_body(x_ref, y_ref, o_ref, acc_ref, *, n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32),
                            y_ref[...].astype(jnp.float32),
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _tile(dim: int, want: int) -> int:
    """Largest tile <= ``want`` dividing ``dim`` (dims are multiples of 128,
    so 128 always does)."""
    t = min(want, dim)
    while dim % t:
        t //= 2
    return t


def matmul(x: jax.Array, y: jax.Array, *, bm: int = 256, bk: int = 512,
           bn: int = 256, interpret: bool = False) -> jax.Array:
    """Tiled (M,K)@(K,N) with f32 accumulation.  Dims must be multiples of
    128 (callers pad); each tile is the largest power-of-two fraction of
    the requested size that divides its dim."""
    M, K = x.shape
    K2, N = y.shape
    assert K == K2
    bm, bk, bn = _tile(M, bm), _tile(K, bk), _tile(N, bn)
    grid = (M // bm, N // bn, K // bk)
    return pl.pallas_call(
        functools.partial(_matmul_body, n_k=grid[2]),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
                  pl.BlockSpec((bk, bn), lambda i, j, k: (k, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="newton_schulz_matmul",
    )(x, y)
