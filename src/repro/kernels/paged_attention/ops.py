"""Public paged-attention entry points used by the model zoo.

TPU backend -> Pallas kernel reading pages in place through the block
table; otherwise the exact gather-then-masked-attention jnp path, so CPU
tests stay bit-exact against the contiguous decode math
(``ref.masked_gqa_attention`` is shared with ``models.attention``).

Quantized pool storage (int8/fp8 ``kv_dtype``) enters here: the decode
entry quantizes the new token's K/V per head (``repro.core.quant``,
``axis=-1`` so the scale rides the page machinery with a trailing
keepdim), commits quantized values + scales through the block table, and
dequantizes either inside the Pallas page loop (TPU) or inside the ref
gather (elsewhere).  The non-TPU deferred path dense-selects the
quantize->dequantize ROUND-TRIPPED values, so deferred and committed
numerics are identical — greedy parity between the two commit disciplines
still holds by construction; only float-vs-quantized becomes a tolerance
comparison.
"""
from __future__ import annotations

import jax

from repro.core import quant
from repro.kernels.paged_attention import ref
from repro.kernels.paged_attention.kernel import paged_attention_tpu


def paged_attention_decode(q, k_pages, v_pages, k_new, v_new, page, off,
                           block_table, index, *, k_scales=None,
                           v_scales=None, logit_softcap: float = 0.0,
                           shard_fn=None):
    """Fused write + attend for one decode step over the paged pool.

    q: (B,1,H,hd); k_new/v_new: (B,KV,hd) — the new token's K/V; page/off:
    (B,) physical write coordinates (trash-redirected for masked rows).
    ``k_scales``/``v_scales`` ((NP, bs, KV, 1) f32) switch on quantized
    storage: the new K/V is quantized per head here, and the returned
    cache/pending carry the matching per-slot scales.

    TPU: commit the write page-granularly and run the Pallas kernel over
    the pool; returns ``(out, {k_pages, v_pages[, k_scales, v_scales]})``
    with the updated pool.  Elsewhere: attention runs on the gathered
    context with the new K/V selected in densely
    (``paged_attention_decode_deferred_ref``) and the pool write is
    DEFERRED — returned under ``pending`` for the model to commit once per
    step across all scanned layers (one scatter per pool leaf instead of
    one collective per layer).
    """
    quantized = k_scales is not None
    if quantized:
        k_q, k_s = quant.quantize(k_new, axis=-1, dtype=k_pages.dtype)
        v_q, v_s = quant.quantize(v_new, axis=-1, dtype=v_pages.dtype)
        k_w, v_w = k_q, v_q
    else:
        k_w = k_new.astype(k_pages.dtype)
        v_w = v_new.astype(v_pages.dtype)
    if jax.default_backend() == "tpu":
        k_pages = k_pages.at[page, off].set(k_w)
        v_pages = v_pages.at[page, off].set(v_w)
        new_cache = {"k_pages": k_pages, "v_pages": v_pages}
        if quantized:
            k_scales = k_scales.at[page, off].set(k_s)
            v_scales = v_scales.at[page, off].set(v_s)
            new_cache["k_scales"] = k_scales
            new_cache["v_scales"] = v_scales
        out = paged_attention_tpu(
            q, k_pages, v_pages, block_table, index,
            k_scales=k_scales, v_scales=v_scales,
            logit_softcap=logit_softcap)
        return out, new_cache
    if quantized:
        # Deferred dense-select uses the round-tripped values: exactly what
        # a committed page read (q * scale) would yield next step.
        k_sel = quant.dequantize(k_q, k_s)
        v_sel = quant.dequantize(v_q, v_s)
    else:
        k_sel, v_sel = k_new, v_new
    out = ref.paged_attention_decode_deferred_ref(
        q, k_pages, v_pages, k_sel, v_sel, index, block_table,
        k_scales=k_scales, v_scales=v_scales,
        logit_softcap=logit_softcap, shard_fn=shard_fn)
    pending = {"k": k_w, "v": v_w, "page": page, "off": off}
    new_cache = {"k_pages": k_pages, "v_pages": v_pages, "pending": pending}
    if quantized:
        pending["k_scale"] = k_s
        pending["v_scale"] = v_s
        new_cache["k_scales"] = k_scales
        new_cache["v_scales"] = v_scales
    return out, new_cache


def paged_prefill_attention(q, k_pages, v_pages, block_table, ctx_len, *,
                            k_scales=None, v_scales=None,
                            logit_softcap: float = 0.0):
    """Chunked prefill: C queries at positions ctx_len..ctx_len+C-1 over the
    row's pages (which already hold the chunk's own K/V — quantized along
    with their scales by the caller when ``k_scales``/``v_scales`` are
    given).  ``ctx_len`` is a traced scalar, or a per-row (B,) vector for
    the speculative verify path (every row scored at its own cursor).
    Gather + exact masked math on every backend — the chunk matmul is
    already MXU-shaped, so a dedicated prefill kernel buys little; the
    decode step is the page-granular hot path."""
    return ref.paged_prefill_attention_ref(
        q, k_pages, v_pages, block_table, ctx_len,
        k_scales=k_scales, v_scales=v_scales,
        logit_softcap=logit_softcap)
