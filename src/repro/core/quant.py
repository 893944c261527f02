"""Shared symmetric quantization helpers — ONE quantizer, two call sites.

Used by ``distributed.collectives`` (per-tensor int8 gradient compression
on the cross-pod axis) and by the paged KV cache (per-slot-per-head int8 /
fp8 page storage with float32 scales dequantized inside attention).

Conventions:
  * symmetric, zero-point-free: ``scale = max|x| / qmax + eps`` along the
    reduced axes, ``q = round(x / scale)`` clipped to the representable
    range (int8) or cast (fp8 — the cast saturates to ±448 for e4m3fn);
  * ``axis=None`` reduces over the whole tensor (scalar scale — the
    gradient-compression contract); an int/tuple axis keeps dims, so the
    scale broadcasts back against ``q`` without reshapes and rides any
    gather/scatter the quantized tensor itself rides;
  * scales are ALWAYS float32 regardless of the storage dtype.
"""
from __future__ import annotations

from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

Axis = Union[None, int, Tuple[int, ...]]

# Largest representable magnitude per storage dtype (int8 symmetric range;
# fp8 e4m3fn saturates at 448).
_INT8_MAX = 127.0
_FP8_E4M3_MAX = 448.0
_EPS = 1e-12


_QMAX = {jnp.dtype(jnp.int8): _INT8_MAX,
         jnp.dtype(jnp.float8_e4m3fn): _FP8_E4M3_MAX}


def is_quantized(dtype) -> bool:
    """True for storage dtypes that need a scale array (int8 / fp8)."""
    return jnp.dtype(dtype) in _QMAX


def qmax(dtype) -> float:
    dtype = jnp.dtype(dtype)
    if dtype not in _QMAX:
        raise ValueError(f"not a quantized storage dtype: {dtype}")
    return _QMAX[dtype]


def quantize(x: jax.Array, axis: Axis = None,
             dtype=jnp.int8) -> Tuple[jax.Array, jax.Array]:
    """Symmetric quantization of ``x`` to ``dtype``.

    Returns ``(q, scale)`` with ``scale`` float32; ``axis=None`` yields a
    scalar scale, otherwise the reduced dims are KEPT (size 1) so
    ``q.astype(f32) * scale`` broadcasts without reshaping.
    """
    xf = x.astype(jnp.float32)
    m = qmax(dtype)
    if axis is None:
        scale = jnp.max(jnp.abs(xf)) / m + _EPS
    else:
        scale = jnp.max(jnp.abs(xf), axis=axis, keepdims=True) / m + _EPS
    y = xf / scale
    if jnp.dtype(dtype) == jnp.int8:
        q = jnp.clip(jnp.round(y), -m, m).astype(jnp.int8)
    else:                                   # fp8: cast saturates
        q = y.astype(dtype)
    return q, scale


def dequantize(q: jax.Array, scale: jax.Array, dtype=jnp.float32):
    return (q.astype(jnp.float32) * scale).astype(dtype)


_KV_DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8,
              "fp8": jnp.float8_e4m3fn}


def resolve_kv_dtype(name: Optional[str]):
    """Map a ``--kv-dtype`` CLI name to a storage dtype (None -> None,
    i.e. 'use the engine's cache_dtype')."""
    if name is None:
        return None
    if name in _KV_DTYPES:
        return _KV_DTYPES[name]
    raise ValueError(f"unknown kv_dtype {name!r} "
                     f"(choose from f32, bf16, int8, fp8)")
