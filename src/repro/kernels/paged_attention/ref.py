"""Reference implementations for paged attention.

The serving cache is a pool of fixed-size token pages plus a per-row block
table (``repro.train.kv_pool``); attention reads through the table instead
of a contiguous per-row KV buffer.  Two exact jnp paths:

``masked_gqa_attention`` — the grouped-query masked-attention math shared by
the contiguous decode path (``models.attention.attn_decode``) and both paged
paths below.  Keeping ONE implementation is what makes paged-vs-contiguous
greedy parity hold by construction: the only difference between the two
cache layouts is *where the keys come from*, never the attention math.

``paged_attention_ref`` — decode: gather each row's pages into its logical
contiguous layout and run the masked math.  This is the lowering path on
non-TPU backends (tests, dry-run); the Pallas kernel in ``kernel.py`` reads
pages in place on TPU.

``paged_prefill_attention_ref`` — chunked prefill: a chunk of C queries at
absolute positions ``ctx_len..ctx_len+C-1`` attends over the row's gathered
pages (which already contain the chunk's own keys — the caller writes the
chunk's K/V through the block table *before* attending).  One causal rule
``key_slot <= query_pos`` covers both the previously prefilled context and
the in-chunk triangle.

Quantized pool storage (``kv_dtype`` int8/fp8): pages hold quantized values
plus per-slot-per-head float32 scale pages (``repro.core.quant``, trailing
keepdim so scales ride the same block-table gathers as their pages).  Every
paged path below takes optional ``*_scales`` and dequantizes IN the gather
— the float context equals ``q * scale`` exactly, so these jnp paths are
the float mirror the Pallas fused-dequant kernel is checked against.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _softcap(x, cap: float):
    """Gemma2 logit soft-capping (mirrors ``models.common.softcap``)."""
    if cap <= 0.0:
        return x
    return cap * jnp.tanh(x / cap)


def masked_gqa_attention(q, k, v, valid, logit_softcap: float = 0.0):
    """Grouped-query attention with an explicit validity mask.

    q: (B, C, H, hd); k, v: (B, S, KV, hd); valid: (B, C, S) bool.
    Returns (B, C, H, hd).  Exactly the ``attn_decode`` einsum math (scores
    in the compute dtype, softmax in float32), generalized from one query
    (C = 1, the decode step) to a prefill chunk (C > 1).
    """
    B, C, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, C, KV, G, hd)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qg, k) \
        / jnp.sqrt(hd).astype(q.dtype)
    scores = _softcap(scores, logit_softcap)
    scores = jnp.where(valid[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v)
    return out.reshape(B, C, H, hd)


def gather_pages(pages, block_table):
    """pages: (NP, bs, ...); block_table: (B, NB) int32 -> (B, NB * bs, ...).

    Row b's logical token t lives at ``pages[block_table[b, t // bs], t % bs]``;
    the gather lays every row out contiguously (garbage pages — free/trash
    entries — land beyond the row's cursor and are masked by the caller).
    """
    B, NB = block_table.shape
    bs = pages.shape[1]
    g = pages[block_table]                       # (B, NB, bs, ...)
    return g.reshape((B, NB * bs) + pages.shape[2:])


def gather_dequant(pages, scales, block_table, dtype):
    """Gather pages through the table; with ``scales`` (quantized storage)
    dequantize in the gather: the (B, S, ...) float context is
    ``q.astype(f32) * scale`` — scale pages are gathered through the SAME
    table, so shared (radix) pages dequantize identically for every row."""
    g = gather_pages(pages, block_table)
    if scales is None:
        return g.astype(dtype)
    s = gather_pages(scales, block_table)
    return (g.astype(jnp.float32) * s).astype(dtype)


def paged_attention_ref(q, k_pages, v_pages, block_table, index, *,
                        k_scales=None, v_scales=None,
                        logit_softcap: float = 0.0, shard_fn=None):
    """Decode through the block table (exact path).

    q: (B, 1, H, hd); k_pages/v_pages: (NP, bs, KV, hd);
    block_table: (B, NB) int32; index: (B,) int32 — slot s of row b is valid
    iff ``s <= index[b]`` (the new token's K/V were already written at slot
    ``index[b]``).  Returns (B, 1, H, hd).

    ``shard_fn`` (optional) constrains the gathered (B, S, KV, hd) context:
    the pool itself is replicated over the DP axes (any row addresses any
    page), so without a constraint GSPMD would replicate the attention
    compute too; resharding the gather output to batch-over-data keeps the
    per-step attention cost identical to the contiguous layout's.
    """
    B = q.shape[0]
    k = gather_dequant(k_pages, k_scales, block_table, q.dtype)
    v = gather_dequant(v_pages, v_scales, block_table, q.dtype)
    if shard_fn is not None:
        k = shard_fn(k)
        v = shard_fn(v)
    S = k.shape[1]
    valid = (jnp.arange(S)[None, :] <= index[:, None])[:, None, :]  # (B,1,S)
    return masked_gqa_attention(q, k, v, valid, logit_softcap)


def paged_attention_decode_deferred_ref(q, k_pages, v_pages, k_new, v_new,
                                        index, block_table, *,
                                        k_scales=None, v_scales=None,
                                        logit_softcap: float = 0.0,
                                        shard_fn=None):
    """Decode with a DEFERRED pool write (the non-TPU hot path).

    The pool still holds only tokens < index; the new token's K/V
    (k_new/v_new: (B, KV, hd)) is set densely into the *gathered* per-row
    context at slot ``index[b]`` — a shard-local update, unlike a scatter
    into the replicated pool, which costs one collective per layer per
    step on data-parallel meshes.  The caller commits (k_new, v_new) to
    the pool once per step, batched across every layer of the scan
    (``transformer._commit_paged_writes``, called by
    ``transformer.lm_decode_step``).  The attention input is byte-
    identical to the contiguous ``attn_decode``'s cache-after-write, so
    parity holds by construction.  Quantized storage: pass the QUANTIZE-
    THEN-DEQUANTIZE round-tripped new K/V so the dense-selected token
    equals what a committed page read would yield.  Returns (B, 1, H, hd).
    """
    B = q.shape[0]
    k = gather_dequant(k_pages, k_scales, block_table, q.dtype)
    v = gather_dequant(v_pages, v_scales, block_table, q.dtype)
    if shard_fn is not None:
        k = shard_fn(k)
        v = shard_fn(v)
    S = k.shape[1]
    # Elementwise select (not a scatter): stays collective-free under any
    # batch sharding of the gathered context.
    at_new = (jnp.arange(S)[None, :] == index[:, None])[..., None, None]
    k = jnp.where(at_new, k_new.astype(q.dtype)[:, None], k)
    v = jnp.where(at_new, v_new.astype(q.dtype)[:, None], v)
    valid = (jnp.arange(S)[None, :] <= index[:, None])[:, None, :]
    return masked_gqa_attention(q, k, v, valid, logit_softcap)


def paged_prefill_attention_ref(q, k_pages, v_pages, block_table, ctx_len, *,
                                k_scales=None, v_scales=None,
                                logit_softcap: float = 0.0):
    """Chunked-prefill attention through the block table.

    q: (B, C, H, hd) — the chunk's queries at absolute positions
    ``ctx_len + arange(C)``; pages already hold the chunk's own K/V.
    ``ctx_len`` is a traced scalar (one executable serves every chunk
    position) or a per-row ``(B,)`` vector — the verify path of
    speculative decoding scores rows at unrelated cursors in one chunk.
    Valid keys for query t: slots ``s <= t`` (previously prefilled context
    plus the in-chunk causal triangle).  Returns (B, C, H, hd).
    """
    B, C = q.shape[0], q.shape[1]
    k = gather_dequant(k_pages, k_scales, block_table, q.dtype)
    v = gather_dequant(v_pages, v_scales, block_table, q.dtype)
    S = k.shape[1]
    ctx = jnp.asarray(ctx_len, jnp.int32)
    if ctx.ndim == 0:
        ctx = jnp.broadcast_to(ctx, (B,))
    qpos = ctx[:, None] + jnp.arange(C)[None, :]                    # (B, C)
    valid = jnp.arange(S)[None, None, :] <= qpos[:, :, None]        # (B, C, S)
    return masked_gqa_attention(q, k, v, valid, logit_softcap)


def paged_mla_attention_ref(q, latent_pages, block_table, valid, wkv_b,
                            num_kv_heads: int, *, rotate_fn=None,
                            latent_new=None, index=None, latent_scales=None,
                            logit_softcap: float = 0.0, shard_fn=None):
    """MLA attention through the block table: pages hold COMPRESSED
    pre-RoPE latent rows ``(NP, bs, r)``, up-projected to K/V inside the
    gather path.

    q: (B, C, H, hd); valid: (B, C, S) with ``S = NB * bs``; wkv_b:
    ``(r, 2 * KV * hd)`` up-projection.  ``rotate_fn`` (optional) applies
    the caller's position encoding to the re-derived keys at their ABSOLUTE
    slot positions ``0..S-1`` (the pages store pre-RoPE latents — MLA's
    memory win — so keys re-derived from them must be rotated where they
    live, exactly as the contiguous ``attn_decode`` MLA path does).
    ``latent_new``/``index`` mirror the deferred-write decode path: the new
    token's latent ``(B, r)`` is dense-selected into the gathered context at
    slot ``index[b]`` BEFORE up-projection, so the pool commit can be
    batched across layers like the standard K/V deferred path (quantized
    storage: pass the round-tripped latent; ``latent_scales`` dequantizes
    the gathered pages).  Returns (B, C, H, hd).
    """
    B = q.shape[0]
    lat = gather_dequant(latent_pages, latent_scales, block_table, q.dtype)
    if shard_fn is not None:
        lat = shard_fn(lat)
    S = lat.shape[1]
    if latent_new is not None:
        at_new = (jnp.arange(S)[None, :] == index[:, None])[..., None]
        lat = jnp.where(at_new, latent_new.astype(q.dtype)[:, None], lat)
    kv = lat @ wkv_b                                       # (B, S, 2*KV*hd)
    k, v = jnp.split(kv, 2, axis=-1)
    hd = k.shape[-1] // num_kv_heads
    k = k.reshape(B, S, num_kv_heads, hd)
    v = v.reshape(B, S, num_kv_heads, hd)
    if rotate_fn is not None:
        k = rotate_fn(k)
    return masked_gqa_attention(q, k, v, valid, logit_softcap)


def masked_gqa_attention_per_query(q, k, v, valid, logit_softcap: float = 0.0):
    """Grouped-query attention where every query has its OWN key set.

    q: (B, C, H, hd); k, v: (B, C, S, KV, hd) — key s of query c is that
    query's s-th context entry; valid: (B, C, S) bool.  Returns
    (B, C, H, hd).  Same score/softmax math as ``masked_gqa_attention`` —
    the key axis is reduced in the same (slot) order, which is what lets
    the speculative verify path reproduce the sliding-window decode's
    ring-slot-ordered softmax bit for bit: each verify query gathers the
    exact ring state a sequential decode at its position would attend to,
    laid out in the same slot order.
    """
    B, C, H, hd = q.shape
    KV = k.shape[3]
    G = H // KV
    qg = q.reshape(B, C, KV, G, hd)
    scores = jnp.einsum("bqkgh,bqskh->bkgqs", qg, k) \
        / jnp.sqrt(hd).astype(q.dtype)
    scores = _softcap(scores, logit_softcap)
    scores = jnp.where(valid[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bqskh->bqkgh", probs, v)
    return out.reshape(B, C, H, hd)
