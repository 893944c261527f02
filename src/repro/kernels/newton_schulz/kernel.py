"""Pallas TPU kernels for Muon's Newton–Schulz orthogonalization.

Two paths:

  * ``ns_fused`` — the whole matrix resides in VMEM; all quintic iterations
    run inside one kernel (zero HBM round-trips between iterations).  Its
    VMEM use is bounded by ``fused_vmem_bytes`` and the kernel asks the
    compiler for exactly that much (``FUSED_VMEM_LIMIT`` at most), so
    ``ops.fits_fused`` and the compiler agree on which matrices it takes.
    The inner dots hit the MXU; n and m are padded to multiples of 128 by
    the caller.

  * ``matmul`` — tiled (bm×bk)·(bk×bn) f32 matmul, used to compose NS
    iterations for matrices too large to fuse (GPT-2's 768×3072 MLP and
    50304×768 embedding, every matrix of the 3072-wide models).  The f32
    output block is the accumulator: its index is fixed over the
    innermost k axis, so it stays in VMEM until written.  ``_plan``
    chooses the tiles from (M, K, N) alone.  An output dim up to 1536
    is one block; a longer one takes blocks of 1024 to 1536 (the side
    that reaches least past the edge, the largest of equals).  A
    (bm, bk)·(bk, bn) step does bm·bn / (2(bm + bn)) FLOPs per fetched
    byte, 256 at 1024 × 1024 and 384 at 1536 × 1536, above v5e's ridge
    of 240.  A dim the block does not divide (the 50304 = 393·128
    vocabulary) takes a ``cdiv`` grid whose last block reaches past the
    edge: what it reads there reaches only outputs past the edge, which
    are never written.  bk is the first of 512, 256, 384 and 128 that
    divides K, so every slice of the contraction is summed once, in
    power-of-two slices wherever one divides.  The kernel
    asks the compiler for its blocks' VMEM (``matmul_vmem_bytes``).
    With ``transpose_rhs`` it contracts the last dims of both blocks, so
    the Gram matrix x·xᵀ is read from x itself, with no transposed copy
    in HBM.
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

LANES = 128
# Output block sides: a (bm, bk)·(bk, bn) step does 2·bm·bn·bk FLOPs on
# 4·bk·(bm + bn) fetched bytes, bm·bn / (2(bm + bn)) FLOP/byte: 256 at
# 1024 × 1024 and 384 at 1536 × 1536, above v5e's ridge of 197 TFLOP/s /
# 819 GB/s = 240.
BLOCK_MIN, BLOCK_MAX = 1024, 1536
# Contraction slices, in order of preference: the first that divides K.
# Powers of two first; 384 before 128, whose 512-byte rows make slow DMAs.
BKS = (512, 256, 384, 128)
# VMEM the tiled kernel may claim; BLOCK_MAX blocks at bk 512 take 41 MB.
MATMUL_VMEM_LIMIT = 64 * 2**20
_NN = (((1,), (0,)), ((), ()))           # a @ b
_NT = (((1,), (1,)), ((), ()))           # a @ b.T


def _side(dim: int) -> int:
    """Block side for an output dim (a multiple of 128): the whole dim up to
    ``BLOCK_MAX``; else the multiple of 128 in [BLOCK_MIN, BLOCK_MAX] whose
    blocks reach least past the edge, the largest of equals."""
    if dim <= BLOCK_MAX:
        return dim
    return min(range(BLOCK_MIN, BLOCK_MAX + 1, LANES),
               key=lambda b: (pl.cdiv(dim, b) * b - dim, -b))


def _plan(M: int, K: int, N: int) -> tuple:
    """(bm, bk, bn) for an (M, K)·(K, N) product, dims multiples of 128."""
    bk = next(b for b in BKS if K % b == 0)
    return _side(M), bk, _side(N)


def matmul_vmem_bytes(bm: int, bk: int, bn: int) -> int:
    """Upper bound on the tiled kernel's VMEM: double-buffered input and
    output blocks and one (bm, bn) product before it is added."""
    return 4 * (2 * bk * (bm + bn) + 3 * bm * bn)


def _matmul_body(x_ref, y_ref, o_ref, *, dims):
    # The f32 output block stays in VMEM over the k axis: it is the
    # accumulator.
    @pl.when(pl.program_id(2) == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jax.lax.dot_general(x_ref[...], y_ref[...], dims,
                                      preferred_element_type=jnp.float32)


def matmul(x: jax.Array, y: jax.Array, *, transpose_rhs: bool = False,
           bm: int | None = None, bk: int | None = None,
           bn: int | None = None, interpret: bool = False) -> jax.Array:
    """Tiled f32 (M,K)@(K,N), or x @ y.T for y (N,K) with ``transpose_rhs``
    (the Gram matrix x·xᵀ from x alone).  Dims must be multiples of 128
    (callers pad).  Tiles default to ``_plan``'s; bk must divide K, while
    M and N may leave a partial last block, whose rows and columns past
    the edge are never written."""
    M, K = x.shape
    N, K2 = y.shape if transpose_rhs else y.shape[::-1]
    if K != K2 or x.dtype != jnp.float32 or y.dtype != jnp.float32:
        raise ValueError(f"matmul wants f32 operands with one K: {x.shape} "
                         f"{x.dtype}, {y.shape} {y.dtype}")
    pm, pk, pn = _plan(M, K, N)
    bm, bk, bn = bm or pm, bk or pk, bn or pn
    if K % bk:
        raise ValueError(f"bk {bk} does not divide K {K}")
    if transpose_rhs:
        y_spec = pl.BlockSpec((bn, bk), lambda i, j, k: (j, k))
    else:
        y_spec = pl.BlockSpec((bk, bn), lambda i, j, k: (k, j))
    return pl.pallas_call(
        functools.partial(_matmul_body, dims=_NT if transpose_rhs else _NN),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        grid=(pl.cdiv(M, bm), pl.cdiv(N, bn), K // bk),
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)), y_spec],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=min(MATMUL_VMEM_LIMIT,
                                 max(matmul_vmem_bytes(bm, bk, bn),
                                     16 * 2**20))),
        interpret=interpret,
        name="newton_schulz_matmul",
    )(x, y)
