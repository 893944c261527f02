"""Pallas TPU flash attention (causal / sliding-window / softcap / GQA),
forward and backward.

Layout: the kernels work on head-major ``(B, H, S, hd)`` arrays, so every
block's last two dims are ``(block, hd)`` — a multiple of 8 by ``hd``, or
the whole ``hd`` — which is the tiling the TPU compiler accepts.  The
public wrapper takes the model's ``(B, S, H, hd)`` layout and transposes.

Forward grid: (batch, q_heads, q_blocks, k_blocks) — the k axis is innermost
and sequential; online-softmax statistics (m, l) and the output accumulator
live in VMEM scratch carried across k iterations, and the row
log-sum-exp is written out for the backward pass.  GQA is handled in the
BlockSpec index map (q head h reads kv head h // group), so K/V are never
repeated in HBM.  Sliding-window and causal constraints are in-kernel
masks; fully-masked blocks are skipped via ``pl.when`` so they cost no MXU
work.

Backward (FlashAttention-2): two kernels recompute the probabilities from
the saved log-sum-exp — ``dq`` walks k blocks for each q block, ``dk/dv``
walks q blocks for each k block — so nothing of size S x S is ever stored.
For GQA, dk/dv come out per q head and are summed over each group outside
the kernel.  ``flash_attention_tpu`` is a ``jax.custom_vjp`` over the two.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_NT = (((1,), (1,)), ((), ()))           # contract the last dims: a @ b.T


def _block(n: int, want: int) -> int:
    """Largest block <= ``want`` that divides ``n`` and is a multiple of 8;
    the whole length when none is (a block equal to the array dim is always
    a legal tile)."""
    b = min(want, n)
    while b >= 8:
        if n % b == 0 and b % 8 == 0:
            return b
        b //= 2
    return n


def _live(q_start, k_start, *, causal, window, block_q, block_k):
    """False iff the (q block, k block) tile is entirely masked."""
    live = jnp.asarray(True)
    if causal:
        live &= k_start <= q_start + block_q - 1
    if window > 0:
        live &= q_start - (k_start + block_k - 1) < window
    return live


def _scores(q, k, q_start, k_start, *, scale, causal, window, softcap):
    """Masked (softcapped) scores of one tile, plus the mask and the tanh
    the softcap backward needs."""
    s = jax.lax.dot_general(q * scale, k, _NT,
                            preferred_element_type=jnp.float32)
    t = None
    if softcap > 0:
        t = jnp.tanh(s / softcap)
        s = softcap * t
    qi = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    ki = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = jnp.ones(s.shape, jnp.bool_)
    if causal:
        mask &= qi >= ki
    if window > 0:
        mask &= (qi - ki) < window
    return jnp.where(mask, s, NEG_INF), mask, t


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_body(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
              scale, causal, window, softcap, block_q, block_k, n_k):
    qb, kb = pl.program_id(2), pl.program_id(3)
    q_start, k_start = qb * block_q, kb * block_k

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(_live(q_start, k_start, causal=causal, window=window,
                   block_q=block_q, block_k=block_k))
    def _compute():
        q = q_ref[...].astype(jnp.float32)                       # (bq, hd)
        k = k_ref[...].astype(jnp.float32)                       # (bk, hd)
        v = v_ref[...].astype(jnp.float32)
        s, _, _ = _scores(q, k, q_start, k_start, scale=scale, causal=causal,
                          window=window, softcap=softcap)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kb == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[...] = m_ref[...] + jnp.log(l)


def _fwd(q, k, v, *, causal, window, softcap, block_q, block_k, interpret):
    """q: (B,H,Sq,hd); k,v: (B,KV,Sk,hd) -> (o (B,H,Sq,hd), lse (B,H,Sq,1))."""
    B, H, Sq, hd = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    G = H // KV
    bq, bk = _block(Sq, block_q), _block(Sk, block_k)
    n_q, n_k = Sq // bq, Sk // bk
    kernel = functools.partial(
        _fwd_body, scale=1.0 / (hd ** 0.5), causal=causal, window=window,
        softcap=softcap, block_q=bq, block_k=bk, n_k=n_k)
    q_spec = pl.BlockSpec((None, None, bq, hd), lambda b, h, i, j: (b, h, i, 0))
    kv_spec = pl.BlockSpec((None, None, bk, hd),
                           lambda b, h, i, j: (b, h // G, j, 0))
    lse_spec = pl.BlockSpec((None, None, bq, 1), lambda b, h, i, j: (b, h, i, 0))
    return pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_k),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, lse_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((B, H, Sq, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def _probs_and_dscores(q, k, v, do, lse, di, q_start, k_start, *, scale,
                       causal, window, softcap):
    """Recompute one tile's probabilities from the saved log-sum-exp and
    return (p, ds) with ds = dL/d(raw scores) (softcap chain applied)."""
    s, mask, t = _scores(q, k, q_start, k_start, scale=scale, causal=causal,
                         window=window, softcap=softcap)
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)                    # (bq, bk)
    dp = jax.lax.dot_general(do, v, _NT, preferred_element_type=jnp.float32)
    ds = p * (dp - di)
    if softcap > 0:
        ds = ds * (1.0 - t * t)
    return p, ds


def _dq_body(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, acc_ref, *,
             scale, causal, window, softcap, block_q, block_k, n_k):
    qb, kb = pl.program_id(2), pl.program_id(3)
    q_start, k_start = qb * block_q, kb * block_k

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(_live(q_start, k_start, causal=causal, window=window,
                   block_q=block_q, block_k=block_k))
    def _compute():
        k = k_ref[...].astype(jnp.float32)
        _, ds = _probs_and_dscores(
            q_ref[...].astype(jnp.float32), k, v_ref[...].astype(jnp.float32),
            do_ref[...].astype(jnp.float32), lse_ref[...], di_ref[...],
            q_start, k_start, scale=scale, causal=causal, window=window,
            softcap=softcap)
        acc_ref[...] += jax.lax.dot(ds, k, preferred_element_type=jnp.float32)

    @pl.when(kb == n_k - 1)
    def _finalize():
        dq_ref[...] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _dkv_body(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
              dk_acc, dv_acc, *, scale, causal, window, softcap, block_q,
              block_k, n_q):
    kb, qb = pl.program_id(2), pl.program_id(3)
    q_start, k_start = qb * block_q, kb * block_k

    @pl.when(qb == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(_live(q_start, k_start, causal=causal, window=window,
                   block_q=block_q, block_k=block_k))
    def _compute():
        q = q_ref[...].astype(jnp.float32)
        do = do_ref[...].astype(jnp.float32)
        p, ds = _probs_and_dscores(
            q, k_ref[...].astype(jnp.float32), v_ref[...].astype(jnp.float32),
            do, lse_ref[...], di_ref[...], q_start, k_start, scale=scale,
            causal=causal, window=window, softcap=softcap)
        dv_acc[...] += jax.lax.dot(p.T, do, preferred_element_type=jnp.float32)
        dk_acc[...] += jax.lax.dot(ds.T, q, preferred_element_type=jnp.float32)

    @pl.when(qb == n_q - 1)
    def _finalize():
        dk_ref[...] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _bwd(q, k, v, o, lse, do, *, causal, window, softcap, block_q, block_k,
         interpret):
    B, H, Sq, hd = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    G = H // KV
    bq, bk = _block(Sq, block_q), _block(Sk, block_k)
    n_q, n_k = Sq // bq, Sk // bk
    kw = dict(scale=1.0 / (hd ** 0.5), causal=causal, window=window,
              softcap=softcap, block_q=bq, block_k=bk)
    di = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                 keepdims=True)                                  # (B,H,Sq,1)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))

    # dq: grid (b, h, q block, k block), k innermost.
    q_spec = pl.BlockSpec((None, None, bq, hd), lambda b, h, i, j: (b, h, i, 0))
    kv_spec = pl.BlockSpec((None, None, bk, hd),
                           lambda b, h, i, j: (b, h // G, j, 0))
    row_spec = pl.BlockSpec((None, None, bq, 1), lambda b, h, i, j: (b, h, i, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_body, n_k=n_k, **kw),
        grid=(B, H, n_q, n_k),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)],
        compiler_params=params, interpret=interpret,
        name="flash_attention_dq",
    )(q, k, v, do, lse, di)

    # dk/dv per q head: grid (b, h, k block, q block), q innermost.
    q_spec = pl.BlockSpec((None, None, bq, hd), lambda b, h, j, i: (b, h, i, 0))
    kv_spec = pl.BlockSpec((None, None, bk, hd),
                           lambda b, h, j, i: (b, h // G, j, 0))
    row_spec = pl.BlockSpec((None, None, bq, 1), lambda b, h, j, i: (b, h, i, 0))
    out_spec = pl.BlockSpec((None, None, bk, hd), lambda b, h, j, i: (b, h, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_body, n_q=n_q, **kw),
        grid=(B, H, n_k, n_q),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((B, H, Sk, hd), k.dtype),
                   jax.ShapeDtypeStruct((B, H, Sk, hd), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, hd), jnp.float32),
                        pltpu.VMEM((bk, hd), jnp.float32)],
        compiler_params=params, interpret=interpret,
        name="flash_attention_dkv",
    )(q, k, v, do, lse, di)
    if G > 1:                       # sum each KV head's group of q heads
        dk = dk.reshape(B, KV, G, Sk, hd).sum(2)
        dv = dv.reshape(B, KV, G, Sk, hd).sum(2)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Public entry (model layout) with a Pallas backward
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _attention(q, k, v, causal, window, softcap, block_q, block_k, interpret):
    o, _ = _fwd(q, k, v, causal=causal, window=window, softcap=softcap,
                block_q=block_q, block_k=block_k, interpret=interpret)
    return o


def _attention_fwd(q, k, v, causal, window, softcap, block_q, block_k,
                   interpret):
    o, lse = _fwd(q, k, v, causal=causal, window=window, softcap=softcap,
                  block_q=block_q, block_k=block_k, interpret=interpret)
    return o, (q, k, v, o, lse)


def _attention_bwd(causal, window, softcap, block_q, block_k, interpret, res,
                   do):
    q, k, v, o, lse = res
    return _bwd(q, k, v, o, lse, do, causal=causal, window=window,
                softcap=softcap, block_q=block_q, block_k=block_k,
                interpret=interpret)


_attention.defvjp(_attention_fwd, _attention_bwd)


def flash_attention_tpu(q, k, v, *, causal=True, window=0, logit_softcap=0.0,
                        block_q=256, block_k=256, interpret=False):
    """q: (B,Sq,H,hd); k,v: (B,Sk,KV,hd) -> (B,Sq,H,hd).  Differentiable:
    the backward pass is the Pallas dq and dk/dv kernels above."""
    t = lambda x: jnp.swapaxes(x, 1, 2)
    o = _attention(t(q), t(k), t(v), causal, window, float(logit_softcap),
                   block_q, block_k, interpret)
    return t(o)
