"""Round-trip units for the shared symmetric quantizer (``core.quant``).

One quantizer, two call sites — gradient compression on the cross-pod
axis and int8/fp8 KV-page storage — so its contract is pinned here once:
symmetric zero-point-free scales (always float32), ``axis=None`` scalar
scales vs kept-dims per-axis scales that broadcast without reshapes,
round-to-nearest error bounded by half a scale step (int8), fp8 cast
saturation at +-448, and the ``--kv-dtype`` CLI name resolution.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quant

F8 = jnp.float8_e4m3fn


def _rand(shape, seed=0, lo=-3.0, hi=3.0):
    rng = np.random.default_rng(seed)
    return rng.uniform(lo, hi, size=shape).astype(np.float32)


def test_int8_roundtrip_scalar_scale():
    x = _rand((64, 8))
    q, s = quant.quantize(x, axis=None, dtype=jnp.int8)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    assert np.ndim(s) == 0
    # symmetric round-to-nearest: error <= scale/2 everywhere, and the
    # largest magnitude lands on +-127
    err = np.abs(np.asarray(quant.dequantize(q, s)) - x)
    assert err.max() <= float(s) * 0.5 + 1e-7
    assert int(np.abs(np.asarray(q)).max()) == 127


@pytest.mark.parametrize("axis", [-1, (0, 2)])
def test_int8_roundtrip_per_axis_keepdims(axis):
    """Reduced dims are KEPT (size 1) so ``q * scale`` broadcasts back
    with no reshape — the property the per-slot-per-head KV scale arrays
    rely on."""
    x = _rand((6, 4, 8), seed=1)
    q, s = quant.quantize(x, axis=axis, dtype=jnp.int8)
    want = list(x.shape)
    for a in (axis if isinstance(axis, tuple) else (axis,)):
        want[a] = 1
    assert list(s.shape) == want and s.dtype == jnp.float32
    err = np.abs(np.asarray(quant.dequantize(q, s)) - x)
    assert (err <= np.asarray(s) * 0.5 + 1e-7).all()


def test_quantize_zero_tensor_is_exact():
    q, s = quant.quantize(jnp.zeros((4, 4)), axis=-1)
    assert not np.asarray(q).any()
    assert not np.asarray(quant.dequantize(q, s)).any()


def test_int8_clips_instead_of_wrapping():
    """An exactly-at-max value maps to +-127; nothing ever wraps."""
    x = jnp.asarray([[-5.0, 5.0, 2.5, 0.0]])
    q, s = quant.quantize(x, axis=None)
    qv = np.asarray(q)
    assert qv.min() == -127 and qv.max() == 127
    assert abs(float(quant.dequantize(q, s)[0, 2]) - 2.5) <= float(s) * 0.5


def test_dequantize_output_dtype():
    q, s = quant.quantize(_rand((8,)), axis=None)
    assert quant.dequantize(q, s).dtype == jnp.float32
    assert quant.dequantize(q, s, jnp.bfloat16).dtype == jnp.bfloat16


def test_fp8_roundtrip_and_saturation():
    """fp8 e4m3fn: 3 mantissa bits -> relative error <= ~2^-4 after the
    max-scaling; out-of-range values saturate at +-448 * scale instead of
    becoming inf."""
    x = _rand((32, 16), seed=2)
    q, s = quant.quantize(x, axis=-1, dtype=F8)
    assert q.dtype == jnp.dtype(F8) and s.dtype == jnp.float32
    deq = np.asarray(quant.dequantize(q, s))
    rel = np.abs(deq - x) / np.maximum(np.abs(x), 1e-3)
    assert rel.max() <= 0.07
    assert np.isfinite(deq).all()


def test_qmax_and_is_quantized():
    assert quant.qmax(jnp.int8) == 127.0
    assert quant.is_quantized(jnp.int8)
    assert not quant.is_quantized(jnp.float32)
    assert not quant.is_quantized(jnp.bfloat16)
    with pytest.raises(ValueError, match="not a quantized"):
        quant.qmax(jnp.float32)
    assert quant.qmax(F8) == 448.0
    assert quant.is_quantized(F8)


def test_resolve_kv_dtype_names():
    assert quant.resolve_kv_dtype(None) is None
    assert quant.resolve_kv_dtype("f32") == jnp.float32
    assert quant.resolve_kv_dtype("bf16") == jnp.bfloat16
    assert quant.resolve_kv_dtype("int8") == jnp.int8
    with pytest.raises(ValueError, match="unknown kv_dtype"):
        quant.resolve_kv_dtype("int4")
    assert quant.resolve_kv_dtype("fp8") == jnp.dtype(F8)
