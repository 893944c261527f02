"""Jit'd public wrapper for Newton–Schulz orthogonalization.

Dispatch: TPU backend -> Pallas (fused kernel when the matrix fits the VMEM
the fused kernel may claim, tiled-matmul composition otherwise); other
backends -> jnp reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.newton_schulz import kernel as K
from repro.kernels.newton_schulz.ref import NS_COEFFS, newton_schulz_ref


def fits_fused(n: int, m: int) -> bool:
    """True iff an (n, m) f32 matrix (padded) fits the fused kernel's VMEM
    limit."""
    return K.fused_vmem_bytes(n, m) <= K.FUSED_VMEM_LIMIT


def _pad_to(x, mult: int = 128):
    n, m = x.shape
    pn, pm = (-n) % mult, (-m) % mult
    if pn or pm:
        x = jnp.pad(x, ((0, pn), (0, pm)))
    return x, (n, m)


def _ns_tiled(x: jax.Array, steps: int, interpret: bool) -> jax.Array:
    """NS via tiled Pallas matmuls for matrices too large to fuse; the Gram
    matrix x·xᵀ is read from x itself (``transpose_rhs``)."""
    a, b, c = NS_COEFFS
    mm = functools.partial(K.matmul, interpret=interpret)
    x = x / (jnp.linalg.norm(x) + 1e-7)
    for _ in range(steps):
        gram = mm(x, x, transpose_rhs=True)
        poly = b * gram + c * mm(gram, gram)
        x = a * x + mm(poly, x)
    return x


def newton_schulz_pallas(m: jax.Array, steps: int = 5,
                         interpret: bool = False) -> jax.Array:
    """The Pallas path: orthogonalize (n_in, n_out) with the wide side last,
    padded to multiples of 128."""
    x = m.astype(jnp.float32)
    transpose = x.shape[0] > x.shape[1]
    if transpose:
        x = x.T
    x, (n0, m0) = _pad_to(x)
    if fits_fused(*x.shape):
        # Padding keeps the Frobenius norm and the Gram spectrum: NS of the
        # padded matrix restricted to the original block equals NS(x).
        y = K.ns_fused(x, steps=steps, interpret=interpret)
    else:
        y = _ns_tiled(x, steps, interpret)
    y = y[:n0, :m0]
    if transpose:
        y = y.T
    return y.astype(m.dtype)


@functools.partial(jax.jit, static_argnames=("steps",))
def newton_schulz(m: jax.Array, steps: int = 5) -> jax.Array:
    """Orthogonalize one matrix (n_in, n_out)."""
    if jax.default_backend() == "tpu":
        return newton_schulz_pallas(m, steps)
    return newton_schulz_ref(m, steps)
