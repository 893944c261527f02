"""Pallas TPU paged-attention decode kernel.

One new query per row attends over that row's KV pages through a block
table, without ever materializing the row's contiguous KV layout in HBM:

  * grid = (batch, logical_blocks) with the block axis innermost and
    sequential; per-KV-head online-softmax statistics (m, l) and the output
    accumulator live in VMEM scratch carried across block iterations — the
    same discipline as ``kernels.flash_attention.kernel``;
  * the block table and per-row cursors are **scalar-prefetched**
    (``PrefetchScalarGridSpec``): the K/V BlockSpec index maps read
    ``table[b, j]`` to DMA the *physical* page backing logical block j of
    row b, so the pipeline fetches pages in block-table order and the
    kernel body never does address arithmetic on HBM;
  * a page is fetched whole, all KV heads at once: the pool's
    ``(NP, bs, KV, hd)`` layout puts the heads in the second-to-last dim,
    and a block of one head there would break the TPU's (8, 128) tiling.
    The kernel then walks the heads of the page in VMEM;
  * GQA folds the query-head group into the q rows (q arrives as
    (B, KV, G, hd)), so pages are fetched once per KV head, never repeated;
  * blocks entirely beyond the row's cursor are skipped via ``pl.when``
    (their DMA still lands, but they cost no MXU/VPU work); the partial
    tail block is masked in-kernel against the cursor.

Free rows point at the pool's trash page — its contents are finite garbage,
so a skipped/masked read never poisons live rows (per-row math only).

Quantized pool storage (int8/fp8 pages + per-slot-per-head f32 scale pages)
adds a dequant step inside the page-iteration loop: the scale pages are
extra block operands indexed through the SAME block-table map as the K/V
pages, so dequantization happens after the f32 cast and before the score
matmul, and the online-softmax accumulation is unchanged.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _body(table_ref, index_ref, q_ref, k_ref, v_ref, *rest, scale: float,
          softcap: float, bs: int, n_blocks: int, kv_heads: int,
          quantized: bool):
    if quantized:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    idx = index_ref[b]                    # row cursor: slots <= idx are valid
    base = j * bs

    @pl.when(base <= idx)
    def _compute():
        k_page = k_ref[...].astype(jnp.float32)                # (bs, KV, hd)
        v_page = v_ref[...].astype(jnp.float32)
        if quantized:
            # Fused dequant inside the page loop: the per-slot f32 scale
            # page arrived through the same block-table-indexed DMA as its
            # K/V page; (bs, KV, 1) broadcasts over (bs, KV, hd).
            k_page = k_page * ks_ref[...]
            v_page = v_page * vs_ref[...]
        for h in range(kv_heads):
            q = q_ref[h].astype(jnp.float32) * scale           # (G, hd)
            k = k_page[:, h, :]                                # (bs, hd)
            v = v_page[:, h, :]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if softcap > 0:
                s = softcap * jnp.tanh(s / softcap)
            slot = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(slot <= idx, s, NEG_INF)             # (G, bs)

            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * alpha + p.sum(-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot(
                p, v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(j == n_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_attention_tpu(q, k_pages, v_pages, block_table, index, *,
                        k_scales=None, v_scales=None,
                        logit_softcap: float = 0.0, interpret: bool = False):
    """q: (B, 1, H, hd); k_pages/v_pages: (NP, bs, KV, hd);
    block_table: (B, NB) int32; index: (B,) int32 (valid slots <= index).
    ``k_scales``/``v_scales`` ((NP, bs, KV, 1) f32, quantized storage)
    switch on the fused-dequant body.  Returns (B, 1, H, hd).

    The scale pages ride the SAME block-table-indexed BlockSpec as their
    K/V pages rather than the scalar-prefetch channel: (NP * bs * KV) f32
    scales scale with the pool and would blow the SMEM budget that the
    (small, per-row) block table and cursors live in, while as block
    operands they simply join the existing page DMA stream.
    """
    B, _, H, hd = q.shape
    bs, KV = k_pages.shape[1], k_pages.shape[2]
    G = H // KV
    NB = block_table.shape[1]
    quantized = k_scales is not None

    # Fold the GQA group into q's row dim: head h = kv * G + g.
    qg = q.reshape(B, KV, G, hd)

    kernel = functools.partial(_body, scale=1.0 / (hd ** 0.5),
                               softcap=logit_softcap, bs=bs, n_blocks=NB,
                               kv_heads=KV, quantized=quantized)
    page_spec = pl.BlockSpec((None, bs, KV, hd),
                             lambda b, j, tbl, idx: (tbl[b, j], 0, 0, 0))
    row_spec = pl.BlockSpec((None, KV, G, hd),
                            lambda b, j, tbl, idx: (b, 0, 0, 0))
    in_specs = [row_spec, page_spec, page_spec]
    operands = [qg, k_pages, v_pages]
    if quantized:
        scale_spec = pl.BlockSpec(
            (None, bs, KV, 1), lambda b, j, tbl, idx: (tbl[b, j], 0, 0, 0))
        in_specs += [scale_spec, scale_spec]
        operands += [k_scales, v_scales]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # block_table, index
        grid=(B, NB),
        in_specs=in_specs,
        out_specs=row_spec,
        scratch_shapes=[pltpu.VMEM((KV, G, hd), jnp.float32),
                        pltpu.VMEM((KV, G, 1), jnp.float32),
                        pltpu.VMEM((KV, G, 1), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_attention_decode",
    )(block_table.astype(jnp.int32), index.astype(jnp.int32), *operands)
    return out.reshape(B, 1, H, hd)
