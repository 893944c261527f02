"""Pallas TPU flash attention (causal / sliding-window / softcap / GQA),
forward and backward.

Layout.  A TPU array is stored in ``T(8, 128)`` tiles of its two minor
dims, so a minor dim narrower than 128 is padded to 128 lanes: a
head-major ``(B, H, S, 64)`` operand takes twice its bytes in HBM and
half of every DMA and VMEM tile is padding, and a ``(..., S, 1)`` row
statistic takes 128 times its bytes.  So the kernels prefer the model's
own layout and keep the statistics dense:

* **Lane-dense path.**  q, k, v, o and their gradients stay ``(B, S,
  H·hd)`` — the model's projection output, of which ``(B, S, H, hd)`` is
  a bitcast — and each block is ``(rows, g·hd)`` lanes holding
  ``g = max(1, 128 // hd)`` whole heads: two at hd 64, one at hd 128 or
  256.  A program computes each of its heads from that head's lanes (the
  other heads' lanes of one operand are zeroed before the contraction,
  which adds exact zeros, and each head's result is kept on its own lanes
  or rows); the per-head arithmetic is the head-major path's.  Taken when
  the g heads of a q block read one k/v block: MHA with ``128 % hd == 0``
  and ``H % g == 0``, or any ``hd % 128 == 0`` (GQA is then an index map,
  q head h reading kv head h // group).  Configs: gpt2-* (12 × 64),
  whisper-base (8 × 64), starcoder2-3b, qwen2-vl-2b, yi-34b, jamba,
  deepseek-moe, moonshot (hd 128), gemma2/3 (hd 256).
* **Head-major path.**  Every other shape — GQA at hd 64 (the paper's
  llama3/qwen3/mixtral/deepseekv3 testbeds), an odd head count at hd 64,
  the smoke configs (at most 4 heads of 16) — is transposed to ``(B, H, S, hd)`` with
  blocks ``(rows, hd)``: the same kernels with one head per program.

``_plan`` makes the choice from the shapes alone.  Both paths keep the
per-row statistics (log-sum-exp, and ``di = rowsum(do * o)`` for the
backward) as ``(B, H / g, g, S)``: S on the lanes, so a q block's
statistics are a dense ``(g, block_q)`` tile; the q block is therefore
a multiple of 128 rows or all of them.

Every kernel works on transposed score tiles, (k rows, q columns): the
softmax statistics are then (1, block_q) rows that broadcast over
sublanes as they are stored, and the forward's max and sum reduce over
sublanes, not across lanes.  The forward's and dq's accumulators are
transposed too (o^T and dq^T, head j on rows j·hd to (j+1)·hd, so each
head's matmul streams only its own hd rows) and are transposed back once
per q block.

Forward grid: (batch, head blocks, q blocks, k blocks) — the k axis is
innermost and sequential; online-softmax statistics (m, l) and the output
accumulator live in VMEM scratch carried across k iterations, and the
log-sum-exp is written out for the backward pass.  Sliding-window and
causal constraints are in-kernel masks; fully-masked blocks are skipped
via ``pl.when`` so they cost no MXU work, and their index maps name the
block already in VMEM, so they cost no DMA either.

Backward (FlashAttention-2): two kernels recompute the probabilities from
the saved log-sum-exp — ``dq`` walks k blocks for each q block, ``dk/dv``
walks q blocks for each k block — so nothing of size S x S is ever stored.
``dq`` also computes ``di`` from do and o at its first k block, and hands
it to ``dk/dv`` as a statistic.  For GQA, dk/dv come out per q head and
are summed over each group outside the kernel.  ``flash_attention_tpu`` is
a ``jax.custom_vjp`` over the two.  Its residuals o and lse are tagged
with the checkpoint names in ``RESIDUAL_NAMES``, so that a checkpoint
policy can keep them (``models.transformer.remat_policy``): the backward of
a checkpointed layer then recomputes q, k and v from the layer's input but
never re-runs the forward kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
LANE_DENSE, HEAD_MAJOR = "lane_dense", "head_major"
RESIDUAL_NAMES = ("flash_attention_o", "flash_attention_lse")
_NT = (((1,), (1,)), ((), ()))           # contract the last dims: a @ b.T


def _plan(q_shape, k_shape) -> tuple:
    """(layout, heads per block) for q ``(B, Sq, H, hd)`` and k ``(B, Sk,
    KV, hd)``: lane-dense where each 128-lane block of q heads reads one
    k/v block, head-major otherwise (see the module docstring)."""
    H, hd, KV = q_shape[2], q_shape[3], k_shape[2]
    if hd % LANES == 0:
        return LANE_DENSE, 1
    g = LANES // hd
    if LANES % hd == 0 and KV == H and H % g == 0:
        return LANE_DENSE, g
    return HEAD_MAJOR, 1


def _block(n: int, want: int, align: int = 8) -> int:
    """Largest block <= ``want`` that divides ``n`` and is a multiple of
    ``align``; the whole length when none is (a block equal to the array
    dim is always a legal tile)."""
    b = min(want, n)
    while b >= align:
        if n % b == 0 and b % align == 0:
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


def _heads(x, g, hd):
    """x ``(rows, g·hd)`` -> one copy per head with the other heads' lanes
    zeroed (x itself when the block holds one head)."""
    if g == 1:
        return [x]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return [jnp.where(lane // hd == j, x, 0.0) for j in range(g)]


def _by_head(parts, hd):
    """Per-head ``(rows, g·hd)`` results -> one array taking head j's lanes
    from ``parts[j]``."""
    if len(parts) == 1:
        return parts[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, parts[0].shape, 1)
    out = parts[0]
    for j, part in enumerate(parts[1:], 1):
        out = jnp.where(lane >= j * hd, part, out)
    return out


def _row(col):
    """(n, 1) -> (1, n), through one (n, 128) transpose."""
    return jnp.broadcast_to(col, (col.shape[0], LANES)).T[:1]


def _scores(q, k, q_start, k_start, *, scale, causal, window, softcap):
    """Masked (softcapped) scores of one tile, transposed: (k rows, q
    columns).  Also the mask and the tanh the softcap backward needs."""
    s = jax.lax.dot_general(k, q * scale, _NT,
                            preferred_element_type=jnp.float32)
    t = None
    if softcap > 0:
        t = jnp.tanh(s / softcap)
        s = softcap * t
    qi = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    ki = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
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
              g, hd, scale, causal, window, softcap, block_q, block_k, n_k):
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
        q = q_ref[...].astype(jnp.float32)                   # (bq, g·hd)
        vt = v_ref[...].astype(jnp.float32).T                # (g·hd, bk)
        for j, kj in enumerate(_heads(k_ref[...].astype(jnp.float32), g, hd)):
            s, _, _ = _scores(q, kj, q_start, k_start, scale=scale,
                              causal=causal, window=window,
                              softcap=softcap)               # (bk, bq)
            m_prev = m_ref[j]                                # (1, bq)
            m_new = jnp.maximum(m_prev, s.max(axis=0, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[j] = l_ref[j] * alpha + p.sum(0, keepdims=True)
            m_ref[j] = m_new
            rows = slice(j * hd, (j + 1) * hd)
            acc_ref[rows, :] = acc_ref[rows, :] * alpha + jax.lax.dot(
                vt[rows], p, preferred_element_type=jnp.float32)

    @pl.when(kb == n_k - 1)
    def _finalize():
        for j in range(g):
            rows = slice(j * hd, (j + 1) * hd)
            l = jnp.maximum(l_ref[j], 1e-30)
            acc_ref[rows, :] = acc_ref[rows, :] / l
            lse_ref[j:j + 1, :] = m_ref[j] + jnp.log(l)
        o_ref[...] = acc_ref[...].T.astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def _probs_and_dscores(q, k, v, do, lse, di, q_start, k_start, *, scale,
                       causal, window, softcap):
    """Recompute one (k rows, q columns) tile's probabilities from the saved
    log-sum-exp and return (p, ds) with ds = dL/d(raw scores) (softcap
    chain applied).  ``lse`` and ``di`` are (1, bq) rows."""
    s, mask, t = _scores(q, k, q_start, k_start, scale=scale, causal=causal,
                         window=window, softcap=softcap)
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)
    dp = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
    ds = p * (dp - di)
    if softcap > 0:
        ds = ds * (1.0 - t * t)
    return p, ds


def _dq_body(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref, di_ref,
             acc_ref, *, g, hd, scale, causal, window, softcap, block_q,
             block_k, n_k):
    qb, kb = pl.program_id(2), pl.program_id(3)
    q_start, k_start = qb * block_q, kb * block_k

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        prod = do_ref[...].astype(jnp.float32) * o_ref[...].astype(jnp.float32)
        for j, pj in enumerate(_heads(prod, g, hd)):
            di_ref[j:j + 1, :] = _row(pj.sum(-1, keepdims=True))

    @pl.when(_live(q_start, k_start, causal=causal, window=window,
                   block_q=block_q, block_k=block_k))
    def _compute():
        q = q_ref[...].astype(jnp.float32)
        do = do_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        kt = k.T                                             # (g·hd, bk)
        for j, (kj, vj) in enumerate(zip(
                _heads(k, g, hd),
                _heads(v_ref[...].astype(jnp.float32), g, hd))):
            _, ds = _probs_and_dscores(                      # (bk, bq)
                q, kj, vj, do, lse_ref[j:j + 1, :], di_ref[j:j + 1, :],
                q_start, k_start, scale=scale, causal=causal, window=window,
                softcap=softcap)
            rows = slice(j * hd, (j + 1) * hd)
            acc_ref[rows, :] += jax.lax.dot(kt[rows], ds,
                                            preferred_element_type=jnp.float32)

    @pl.when(kb == n_k - 1)
    def _finalize():
        dq_ref[...] = (acc_ref[...] * scale).T.astype(dq_ref.dtype)


def _dkv_body(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
              dk_acc, dv_acc, *, g, hd, scale, causal, window, softcap,
              block_q, block_k, n_q):
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
        dks, dvs = [], []
        for j, (kj, vj) in enumerate(zip(
                _heads(k_ref[...].astype(jnp.float32), g, hd),
                _heads(v_ref[...].astype(jnp.float32), g, hd))):
            p, ds = _probs_and_dscores(                      # (bk, bq)
                q, kj, vj, do, lse_ref[j:j + 1, :], di_ref[j:j + 1, :],
                q_start, k_start, scale=scale, causal=causal, window=window,
                softcap=softcap)
            dvs.append(jax.lax.dot(p, do, preferred_element_type=jnp.float32))
            dks.append(jax.lax.dot(ds, q, preferred_element_type=jnp.float32))
        dv_acc[...] += _by_head(dvs, hd)
        dk_acc[...] += _by_head(dks, hd)

    @pl.when(qb == n_q - 1)
    def _finalize():
        dk_ref[...] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call wrappers (kernel layout)
# ---------------------------------------------------------------------------

class _Geometry:
    """Shapes of one call in the kernel layout: lane-dense q ``(B, Sq,
    H·hd)``, k/v ``(B, Sk, KV·hd)``, or head-major ``(B, H, S, hd)``."""

    def __init__(self, q, k, *, layout, g, hd, block_q, block_k):
        self.layout, self.g, self.hd = layout, g, hd
        if layout == LANE_DENSE:
            (self.B, self.Sq, W), (self.Sk, KVW) = q.shape, k.shape[1:]
            self.H, self.KV = W // hd, KVW // hd
        else:
            self.B, self.H, self.Sq, _ = q.shape
            self.KV, self.Sk = k.shape[1], k.shape[2]
        self.G = self.H // self.KV
        self.bq = _block(self.Sq, block_q, LANES)
        self.bk = _block(self.Sk, block_k)
        self.n_q, self.n_k = self.Sq // self.bq, self.Sk // self.bk

    def spec(self, rows, pick):
        """BlockSpec of a q-like or k-like operand: ``pick`` maps the grid
        index to (batch, head block, sequence block)."""
        width = self.g * self.hd
        if self.layout == LANE_DENSE:
            def index(*ix):
                b, h, s = pick(*ix)
                return b, s, h
            return pl.BlockSpec((None, rows, width), index)
        return pl.BlockSpec((None, None, rows, width),
                            lambda *ix: (*pick(*ix), 0))

    def live_k(self, i, j, causal, window):
        """k block j for q block i, moved to the nearest live one where the
        tile is masked out: a skipped tile then names the block already in
        VMEM, and Pallas issues no DMA for it."""
        if causal:
            j = jnp.minimum(j, ((i + 1) * self.bq - 1) // self.bk)
        if window > 0:
            j = jnp.maximum(j, (i * self.bq - window + 1) // self.bk)
        return jnp.clip(j, 0, self.n_k - 1)

    def live_q(self, j, i, causal, window):
        """q block i for k block j, likewise (see ``live_k``)."""
        if causal:
            i = jnp.maximum(i, (j * self.bk) // self.bq)
        if window > 0:
            i = jnp.minimum(i, ((j + 1) * self.bk + window - 2) // self.bq)
        return jnp.clip(i, 0, self.n_q - 1)

    def stat_spec(self, pick):
        def index(*ix):
            b, h, s = pick(*ix)
            return b, h, 0, s
        return pl.BlockSpec((None, None, self.g, self.bq), index)

    def stat_shape(self):
        return (self.B, self.H // self.g, self.g, self.Sq)

    def kw(self):
        return dict(g=self.g, hd=self.hd, scale=1.0 / (self.hd ** 0.5),
                    block_q=self.bq, block_k=self.bk)


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))


def _fwd(q, k, v, *, layout, g, hd, causal, window, softcap, block_q,
         block_k, interpret):
    """-> (o like q, lse ``(B, H/g, g, Sq)``)."""
    geo = _Geometry(q, k, layout=layout, g=g, hd=hd, block_q=block_q,
                    block_k=block_k)
    G = geo.G
    q_spec = geo.spec(geo.bq, lambda b, h, i, j: (b, h, i))
    kv_spec = geo.spec(geo.bk, lambda b, h, i, j: (
        b, h // G, geo.live_k(i, j, causal, window)))
    width = g * hd
    return pl.pallas_call(
        functools.partial(_fwd_body, causal=causal, window=window,
                          softcap=softcap, n_k=geo.n_k, **geo.kw()),
        grid=(geo.B, geo.H // g, geo.n_q, geo.n_k),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, geo.stat_spec(lambda b, h, i, j: (b, h, i))],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(geo.stat_shape(), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((width, geo.bq), jnp.float32),
                        pltpu.VMEM((g, 1, geo.bq), jnp.float32),
                        pltpu.VMEM((g, 1, geo.bq), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)


def _bwd(q, k, v, o, lse, do, *, layout, g, hd, causal, window, softcap,
         block_q, block_k, interpret):
    geo = _Geometry(q, k, layout=layout, g=g, hd=hd, block_q=block_q,
                    block_k=block_k)
    B, H, KV, G, Sq, Sk = geo.B, geo.H, geo.KV, geo.G, geo.Sq, geo.Sk
    kw = dict(causal=causal, window=window, softcap=softcap, **geo.kw())
    width = g * hd

    # dq: grid (b, head block, q block, k block), k innermost.
    q_spec = geo.spec(geo.bq, lambda b, h, i, j: (b, h, i))
    kv_spec = geo.spec(geo.bk, lambda b, h, i, j: (
        b, h // G, geo.live_k(i, j, causal, window)))
    stat_spec = geo.stat_spec(lambda b, h, i, j: (b, h, i))
    dq, di = pl.pallas_call(
        functools.partial(_dq_body, n_k=geo.n_k, **kw),
        grid=(B, H // g, geo.n_q, geo.n_k),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, q_spec, stat_spec],
        out_specs=[q_spec, stat_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(geo.stat_shape(), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((width, geo.bq), jnp.float32)],
        compiler_params=_PARAMS, interpret=interpret,
        name="flash_attention_dq",
    )(q, k, v, do, o, lse)

    # dk/dv per q head: grid (b, head block, k block, q block), q innermost.
    live_q = lambda b, h, j, i: (b, h, geo.live_q(j, i, causal, window))
    q_spec = geo.spec(geo.bq, live_q)
    kv_spec = geo.spec(geo.bk, lambda b, h, j, i: (b, h // G, j))
    stat_spec = geo.stat_spec(live_q)
    out_spec = geo.spec(geo.bk, lambda b, h, j, i: (b, h, j))
    per_q_head = (k.shape[:-1] + (H * hd,) if layout == LANE_DENSE
                  else (B, H, Sk, hd))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_body, n_q=geo.n_q, **kw),
        grid=(B, H // g, geo.n_k, geo.n_q),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, stat_spec, stat_spec],
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct(per_q_head, k.dtype),
                   jax.ShapeDtypeStruct(per_q_head, v.dtype)],
        scratch_shapes=[pltpu.VMEM((geo.bk, width), jnp.float32),
                        pltpu.VMEM((geo.bk, width), jnp.float32)],
        compiler_params=_PARAMS, interpret=interpret,
        name="flash_attention_dkv",
    )(q, k, v, do, lse, di)
    if G > 1:                       # sum each KV head's group of q heads
        if layout == LANE_DENSE:
            dk = dk.reshape(B, Sk, KV, G, hd).sum(3).reshape(k.shape)
            dv = dv.reshape(B, Sk, KV, G, hd).sum(3).reshape(v.shape)
        else:
            dk = dk.reshape(B, KV, G, Sk, hd).sum(2)
            dv = dv.reshape(B, KV, G, Sk, hd).sum(2)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Public entry (model layout) with a Pallas backward
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=tuple(range(3, 12)))
def _attention(q, k, v, layout, g, hd, causal, window, softcap, block_q,
               block_k, interpret):
    o, _ = _fwd(q, k, v, layout=layout, g=g, hd=hd, causal=causal,
                window=window, softcap=softcap, block_q=block_q,
                block_k=block_k, interpret=interpret)
    return o


def _attention_fwd(q, k, v, layout, g, hd, causal, window, softcap, block_q,
                   block_k, interpret):
    o, lse = _fwd(q, k, v, layout=layout, g=g, hd=hd, causal=causal,
                  window=window, softcap=softcap, block_q=block_q,
                  block_k=block_k, interpret=interpret)
    o = checkpoint_name(o, RESIDUAL_NAMES[0])
    lse = checkpoint_name(lse, RESIDUAL_NAMES[1])
    return o, (q, k, v, o, lse)


def _attention_bwd(layout, g, hd, causal, window, softcap, block_q, block_k,
                   interpret, res, do):
    q, k, v, o, lse = res
    return _bwd(q, k, v, o, lse, do, layout=layout, g=g, hd=hd,
                causal=causal, window=window, softcap=softcap,
                block_q=block_q, block_k=block_k, interpret=interpret)


_attention.defvjp(_attention_fwd, _attention_bwd)


def flash_attention_tpu(q, k, v, *, causal=True, window=0, logit_softcap=0.0,
                        block_q=256, block_k=256, interpret=False):
    """q: (B,Sq,H,hd); k,v: (B,Sk,KV,hd) -> (B,Sq,H,hd).  Differentiable:
    the backward pass is the Pallas dq and dk/dv kernels above."""
    layout, g = _plan(q.shape, k.shape)
    hd = q.shape[-1]
    if layout == LANE_DENSE:
        to = lambda x: x.reshape(x.shape[:2] + (-1,))     # a bitcast
        back = lambda x: x.reshape(q.shape)
    else:
        to = back = lambda x: jnp.swapaxes(x, 1, 2)
    o = _attention(to(q), to(k), to(v), layout, g, hd, causal, window,
                   float(logit_softcap), block_q, block_k, interpret)
    return back(o)
