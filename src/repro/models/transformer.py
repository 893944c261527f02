"""Scan-stacked decoder-only LM covering dense / GQA / MLA / sliding-window /
softcap / MoE / Mamba-hybrid / RWKV architectures.

Layer stacking
--------------
Layers are grouped into *super-blocks* of length ``cfg.pattern_period`` (1 for
homogeneous stacks, 8 for Jamba's 1-attn:7-mamba pattern, 6 for Gemma3's 5:1
local:global pattern...).  Every super-block has an identical pytree
structure, so the stack is a single pytree whose leaves carry a leading
``n_super = num_layers // period`` axis consumed by ``jax.lax.scan``:

  * HLO size is depth-independent (critical for 60-layer dry-run compiles),
  * progressive depth expansion (the paper's technique) is a pure reshape/
    concat on the leading axis — identical machinery for all 10 archs.

Zero-layer models (`n_super == 0`) skip the scan entirely: the model is
[Embedding, LM_head(+norm)] exactly as in the paper's footnote 1.
"""
from __future__ import annotations

from typing import Callable, Literal, NamedTuple, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.kernels.flash_attention.kernel import RESIDUAL_NAMES
from repro.models import attention as attn
from repro.models import mlp as mlp_mod
from repro.models import ssm as ssm_mod
from repro.models.common import (apply_norm, cross_entropy, dense_init,
                                 embed_init, maybe_shard, norm_init, softcap)

# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _layer_init(key, cfg: ModelConfig, layer_in_period: int, dtype):
    """One layer's params; structure depends only on position-in-period."""
    kind = cfg.layer_kind(layer_in_period)
    ks = jax.random.split(key, 4)
    p = {"ln1": norm_init(cfg.d_model, cfg.norm)}
    if kind == "attn":
        p["attn"] = attn.attn_init(ks[0], cfg, dtype)
        p["ln2"] = norm_init(cfg.d_model, cfg.norm)
        if cfg.layer_is_moe(layer_in_period):
            p["moe"] = mlp_mod.moe_init(ks[1], cfg, dtype)
        else:
            p["mlp"] = mlp_mod.mlp_init(ks[1], cfg, dtype)
    elif kind == "mamba":
        p["mamba"] = ssm_mod.mamba_init(ks[0], cfg, dtype)
        if cfg.layer_is_moe(layer_in_period):
            p["ln2"] = norm_init(cfg.d_model, cfg.norm)
            p["moe"] = mlp_mod.moe_init(ks[1], cfg, dtype)
    elif kind == "rwkv":
        p["rwkv_tm"] = ssm_mod.rwkv_init(ks[0], cfg, dtype)
        p["ln2"] = norm_init(cfg.d_model, cfg.norm)
    else:
        raise ValueError(kind)
    return p


def superblock_init(key, cfg: ModelConfig, dtype=jnp.float32):
    period = cfg.pattern_period
    ks = jax.random.split(key, period)
    return {f"layer{i}": _layer_init(ks[i], cfg, i, dtype)
            for i in range(period)}


def lm_init(key, cfg: ModelConfig, dtype=jnp.float32, num_layers=None):
    """Initialize the full LM at depth `num_layers` (default cfg.num_layers)."""
    L = cfg.num_layers if num_layers is None else num_layers
    period = cfg.pattern_period
    assert L % period == 0, (L, period)
    n_super = L // period
    ks = jax.random.split(key, n_super + 3)
    params = {"embed": embed_init(ks[0], cfg.vocab_size, cfg.d_model, dtype),
              "final_norm": norm_init(cfg.d_model, cfg.norm)}
    if cfg.position == "absolute":
        params["pos_embed"] = (jax.random.normal(ks[1], (cfg.max_seq_len, cfg.d_model))
                               * 0.01).astype(dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[2], cfg.d_model, cfg.vocab_size, dtype)
    if n_super > 0:
        blocks = [superblock_init(ks[3 + i], cfg, dtype) for i in range(n_super)]
        params["blocks"] = jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)
    return params


def num_superblocks(params) -> int:
    if "blocks" not in params:
        return 0
    return jax.tree.leaves(params["blocks"])[0].shape[0]


# ---------------------------------------------------------------------------
# One layer body
# ---------------------------------------------------------------------------


class Mixers(NamedTuple):
    """An entry point's token mixers, one per block kind.  ``attn(p, cfg, h,
    state, window)`` and ``mamba`` / ``rwkv_time`` / ``rwkv_channel(p, cfg,
    h, state)`` each return ``(y, state)``, threading the layer's cache or
    carry.  ``per_token``: recurrent layers are given a multi-token chunk
    and one-token mixers, so :func:`_replay` runs the block per token."""
    attn: Callable
    mamba: Callable
    rwkv_time: Callable
    rwkv_channel: Callable
    per_token: bool = False


def _block(lp, cfg: ModelConfig, i: int, x, mix: Mixers, state):
    """Layer ``i`` of a super-block, for every entry point: its block
    kind's residual structure around the entry point's token mixers.

      attn:  norm -> mixer -> residual -> norm -> MLP or MoE -> residual
      mamba: norm -> mixer -> residual (-> norm -> MoE -> residual)
      rwkv:  norm -> time mix -> residual -> norm -> channel mix -> residual

    Returns (x, state, MoE aux loss); inference drops the loss."""
    kind = cfg.layer_kind(i)
    if kind not in ("attn", "mamba", "rwkv"):
        raise ValueError(kind)
    if kind != "attn" and mix.per_token:
        x, state = _replay(lp, cfg, i, x, mix._replace(per_token=False),
                           state)
        return x, state, jnp.zeros((), jnp.float32)
    aux = jnp.zeros((), jnp.float32)
    h = apply_norm(lp["ln1"], x, cfg.norm)
    if kind == "attn":
        y, state = mix.attn(lp["attn"], cfg, h, state, cfg.layer_window(i))
    elif kind == "mamba":
        y, state = mix.mamba(lp["mamba"], cfg, h, state)
    else:
        y, state = mix.rwkv_time(lp["rwkv_tm"], cfg, h, state)
    x = x + y
    if kind == "mamba" and not cfg.layer_is_moe(i):
        return x, state, aux
    h = apply_norm(lp["ln2"], x, cfg.norm)
    if kind == "rwkv":
        y, state = mix.rwkv_channel(lp["rwkv_tm"], cfg, h, state)
    elif cfg.layer_is_moe(i):
        y, a = mlp_mod.moe_apply(lp["moe"], cfg, h)
        aux = aux + a["aux_loss"] + a["router_zloss"]
    else:
        y = mlp_mod.mlp_apply(lp["mlp"], cfg, h)
    return x + y, state, aux


def _replay(lp, cfg: ModelConfig, i: int, x, mix: Mixers, state):
    """Recurrent layer ``i`` over a (B, C) chunk, one token at a time:
    ``_block`` with one-token mixers under ``lax.scan`` — the exact
    per-token decode math, so the replay is bitwise identical to sequential
    decode.  The state after every token goes into ``pending["states"]``:
    a (C+1)-deep checkpoint ring (entry 0 = the pre-round state) from which
    :func:`lm_spec_commit` rewinds each row to its accepted length with one
    index-select, O(γ·state) memory per layer."""
    def step(s, xt):
        xt, s, _ = _block(lp, cfg, i, xt[:, None, :], mix, s)
        return s, (xt[:, 0], s)
    _, (xs, states) = jax.lax.scan(step, state, jnp.moveaxis(x, 1, 0))
    ring = jax.tree.map(
        lambda s0, ss: jnp.concatenate([s0[None].astype(ss.dtype), ss], 0),
        state, states)
    return jnp.moveaxis(xs, 0, 1), {"pending": {"states": ring}}


# ---------------------------------------------------------------------------
# The stack, the embedding and the head
# ---------------------------------------------------------------------------

_ROWS = P(("pod", "data"), None, None)
_LAYER_OUT = P(("pod", "data"), "model", None)
_LOGITS = P(("pod", "data"), None, "model")

# Activation checkpointing of the layer scan: off, or on with a policy —
# True / "nothing" recomputes every layer's forward except the flash
# attention kernel's output and log-sum-exp, "dots" also saves matmul
# outputs (less recompute, more live memory).
Remat = Union[bool, Literal["nothing", "dots"]]


def remat_policy(remat: Remat):
    """The checkpoint policy of the layer scan for a ``remat`` that is on.
    Both keep the flash kernel's residuals (``RESIDUAL_NAMES``), so the
    backward never re-runs the forward kernel; layers without the kernel
    have no such names."""
    policies = jax.checkpoint_policies
    residuals = policies.save_only_these_names(*RESIDUAL_NAMES)
    if remat == "dots":
        return policies.save_from_both_policies(
            policies.dots_with_no_batch_dims_saveable, residuals)
    return residuals


def _stack(params, cfg: ModelConfig, x, mix: Mixers, state=None,
           remat: Remat = False, shard: bool = True):
    """Every layer, as one ``lax.scan`` over the super-blocks of
    ``params["blocks"]`` together with the per-layer ``state`` tree
    (caches or carries with the leading n_super axis; None for training).
    ``shard`` constrains each layer's output to the activation layout.
    Returns (x, state, summed MoE aux loss)."""
    aux = jnp.zeros((), jnp.float32)
    if num_superblocks(params) == 0:
        return x, state, aux

    def scan_fn(carry, xs):
        (x, aux), (sb, state_sb) = carry, xs
        sb_aux = jnp.zeros((), jnp.float32)
        new_state = {}
        for i in range(cfg.pattern_period):
            name = f"layer{i}"
            x, new_state[name], a = _block(
                sb[name], cfg, i, x, mix,
                None if state_sb is None else state_sb[name])
            if shard:
                x = maybe_shard(x, _LAYER_OUT)
            sb_aux = sb_aux + a
        return (x, aux + sb_aux), new_state
    if remat:
        scan_fn = jax.checkpoint(scan_fn, policy=remat_policy(remat))
    (x, aux), state = jax.lax.scan(scan_fn, (x, aux),
                                   (params["blocks"], state))
    return x, state, aux


def embed_tokens(params, cfg: ModelConfig, tokens, embeds=None, offset=0):
    """Token (+optional precomputed frontend) embedding.  tokens: (B, S_txt);
    embeds (frontend stub output): (B, N_front, d_model) prepended."""
    x = params["embed"][tokens]
    if cfg.position == "absolute":
        S = tokens.shape[1]
        x = x + jax.lax.dynamic_slice_in_dim(params["pos_embed"], offset, S, 0)
    if embeds is not None:
        x = jnp.concatenate([embeds.astype(x.dtype), x], axis=1)
    return x


def _embed_at(params, cfg: ModelConfig, tokens, pos):
    """Embedding of tokens (B, C) at per-row absolute positions pos (B, C)."""
    x = params["embed"][tokens]
    if cfg.position == "absolute":
        x = x + params["pos_embed"][pos]
    return x


def _positions(cfg: ModelConfig, pos, B):
    """Position ids of the attention layers from pos (1 or B, S)."""
    S = pos.shape[1]
    if cfg.position == "mrope":
        return jnp.broadcast_to(pos[None], (3, B, S))      # text-only stub ids
    return jnp.broadcast_to(pos, (B, S))


def _embed_prompt(params, cfg: ModelConfig, tokens, embeds, positions):
    """Embedding and position ids of whole prompts at positions 0..S-1."""
    x = embed_tokens(params, cfg, tokens, embeds)
    B, S = x.shape[0], x.shape[1]
    if positions is None:
        positions = _positions(cfg, jnp.arange(S)[None, :], B)
    return maybe_shard(x, _ROWS), positions


def _head(params, cfg: ModelConfig, x):
    """Final norm -> tied or untied LM head -> logit softcap."""
    x = apply_norm(params["final_norm"], x, cfg.norm)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return softcap(x @ head, cfg.final_logit_softcap)


# ---------------------------------------------------------------------------
# Forward (train)
# ---------------------------------------------------------------------------


def _train_mixers(positions) -> Mixers:
    return Mixers(
        attn=lambda p, cfg, h, s, window: (
            attn.attn_apply(p, cfg, h, positions, window=window), s),
        mamba=lambda p, cfg, h, s: (ssm_mod.mamba_apply(p, cfg, h), s),
        rwkv_time=lambda p, cfg, h, s: (ssm_mod.rwkv_time_mix(p, cfg, h), s),
        rwkv_channel=lambda p, cfg, h, s: (
            ssm_mod.rwkv_channel_mix(p, cfg, h), s))


def lm_apply(params, cfg: ModelConfig, tokens, embeds=None, positions=None,
             remat: Remat = False) -> Tuple[jax.Array, jax.Array]:
    """Returns (logits (B, S_total, V), aux_loss scalar)."""
    x, positions = _embed_prompt(params, cfg, tokens, embeds, positions)
    x, _, aux = _stack(params, cfg, x, _train_mixers(positions), remat=remat)
    return maybe_shard(_head(params, cfg, x), _LOGITS), aux


def lm_loss(params, cfg: ModelConfig, tokens, labels, embeds=None,
            mask=None, remat: Remat = False):
    logits, aux = lm_apply(params, cfg, tokens, embeds=embeds, remat=remat)
    if embeds is not None:                  # loss on the text tail only
        logits = logits[:, embeds.shape[1]:]
    loss = cross_entropy(logits, labels, mask)
    return loss + aux, {"ce": loss, "aux": aux}


# ---------------------------------------------------------------------------
# Serving state: caches and carries
# ---------------------------------------------------------------------------


def _layer_states(params, cfg: ModelConfig, batch: int, attn_state):
    """Per-layer serving state mirroring the super-block stack (leading
    n_super axis): ``attn_state(window)`` for attention layers, the O(1)
    recurrent state of ``batch`` rows for mamba/rwkv layers."""
    n_super = num_superblocks(params)
    if n_super == 0:
        return {}

    def one_layer(i):
        kind = cfg.layer_kind(i)
        if kind == "attn":
            return attn_state(cfg.layer_window(i))
        if kind == "mamba":
            return ssm_mod.mamba_init_state(cfg, batch)
        if kind == "rwkv":
            return ssm_mod.rwkv_init_state(cfg, batch)
        raise ValueError(kind)

    one = {f"layer{i}": one_layer(i) for i in range(cfg.pattern_period)}
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x, (n_super,) + x.shape).copy(), one)


def lm_init_cache(params, cfg: ModelConfig, batch_size: int, max_len: int,
                  dtype=jnp.bfloat16):
    """Cache pytree mirroring the super-block stack (leading n_super axis)."""
    return _layer_states(params, cfg, batch_size, lambda w: attn.init_kv_cache(
        cfg, batch_size, max_len, dtype, window=w))


def lm_init_paged_cache(params, cfg: ModelConfig, batch_size: int,
                        num_blocks: int, block_size: int, max_len: int,
                        dtype=jnp.bfloat16, kv_dtype=None):
    """Paged serve-cache pytree (leading n_super axis, like `lm_init_cache`).

    Full-attention layers hold a GLOBAL pool of ``num_blocks`` pages (+1
    trash page) addressed per row through the engine's block table — their
    leaves carry no batch dim.  Sliding-window layers keep per-row ring
    buffers (already O(window) — paging them buys < one page per row) and
    mamba/rwkv layers keep their O(1) per-row recurrent state; both are
    scattered on admit exactly as in the contiguous engine.

    ``kv_dtype`` overrides the POOL leaves' storage dtype only (int8/fp8
    adds per-slot float32 scale leaves — see ``attn.init_paged_kv_cache``);
    window rings and recurrent state stay in ``dtype``, since they are
    per-row O(window)/O(1) state, not the HBM-dominant paged working set."""
    pool_dtype = dtype if kv_dtype is None else kv_dtype

    def attn_state(w):
        if w > 0:
            return attn.init_kv_cache(cfg, batch_size, max_len, dtype,
                                      window=w)
        return attn.init_paged_kv_cache(cfg, num_blocks, block_size,
                                        pool_dtype)
    return _layer_states(params, cfg, batch_size, attn_state)


def lm_init_prefill_carry(params, cfg: ModelConfig, max_len: int,
                          dtype=jnp.bfloat16):
    """B=1 chunked-prefill carry: the per-row state a prefilling request
    threads between chunks — window rings and recurrent states.  Paged
    layers carry nothing ({}): their K/V goes straight into the shared pool
    through the block table, so admission never copies it."""
    return _layer_states(params, cfg, 1, lambda w: attn.init_kv_cache(
        cfg, 1, max_len, dtype, window=w) if w > 0 else {})


# ---------------------------------------------------------------------------
# Prefill (whole prompt, or one chunk of it)
# ---------------------------------------------------------------------------


# The recurrent mixers of a prefill (whole prompt or one chunk of it) and
# of a decode step (one token, or verify's per-token replay).
_PREFILL = dict(mamba=ssm_mod.mamba_prefill,
                rwkv_time=ssm_mod.rwkv_time_mix_prefill,
                rwkv_channel=ssm_mod.rwkv_channel_mix_prefill)
_DECODE = dict(mamba=ssm_mod.mamba_decode, rwkv_time=ssm_mod.rwkv_decode,
               rwkv_channel=ssm_mod.rwkv_channel_mix_decode)


def lm_prefill(params, cfg: ModelConfig, tokens, cache, embeds=None,
               positions=None) -> Tuple[jax.Array, object]:
    """True full-sequence prefill: ONE forward through the train-path math
    that also fills the decode cache — replacing the O(P) token-by-token
    Python loop.  Returns (logits (B, S_total, V), cache ready for decode at
    per-row cursor ``index = full((B,), S_total)``); a continuous-batching
    engine prefills one request at a time (B=1, the prompt's exact length)
    and scatters the row into a freed slot of the live batch cache."""
    x, positions = _embed_prompt(params, cfg, tokens, embeds, positions)
    mix = Mixers(
        attn=lambda p, cfg, h, s, window: attn.attn_prefill(
            p, cfg, h, s, positions, window), **_PREFILL)
    x, cache, _ = _stack(params, cfg, x, mix, cache)
    return maybe_shard(_head(params, cfg, x), _LOGITS), cache


def lm_prefill_chunk(params, cfg: ModelConfig, tokens, cache, carry,
                     block_table, ctx_len):
    """One chunked-prefill step: tokens (B, C) at absolute positions
    ``ctx_len .. ctx_len + C - 1`` (``ctx_len`` traced — one executable per
    chunk WIDTH, not per offset).  Paged K/V lands in the shared pool of
    ``cache`` through ``block_table`` (B, NB); window rings and recurrent
    states thread through the B=1 ``carry`` exactly as the full prefill
    threads its cache — binary-decomposed chunks are exact (never padded),
    so recurrent states see only real tokens and chunked == one-shot
    prefill numerically.  Returns (last-position logits (B, 1, V), cache,
    carry): only the final chunk's logits are consumed (first-token
    sampling), so the lm_head matmul stays O(1) per chunk."""
    B, C = tokens.shape
    ctx_len = jnp.asarray(ctx_len, jnp.int32)
    x = maybe_shard(embed_tokens(params, cfg, tokens, offset=ctx_len), _ROWS)
    positions = _positions(cfg, ctx_len + jnp.arange(C)[None, :], B)
    mix = Mixers(
        attn=lambda p, cfg, h, s, window: attn.attn_prefill_chunk(
            p, cfg, h, s, ctx_len, positions, window,
            block_table=block_table), **_PREFILL)
    # Each layer threads its carry, or the shared pool where it carries
    # nothing (paged layers); the rest of `cache` is the live batch's.
    pooled = [n for n, c in carry.items() if not c]
    state = {n: cache[n] if n in pooled else c for n, c in carry.items()}
    x, state, _ = _stack(params, cfg, x, mix, state)
    cache = {**cache, **{n: state[n] for n in pooled}}
    carry = {**state, **{n: carry[n] for n in pooled}}
    return _head(params, cfg, x[:, -1:]), cache, carry


# ---------------------------------------------------------------------------
# Speculative verify
# ---------------------------------------------------------------------------


def lm_verify(params, cfg: ModelConfig, tokens, cache, index, block_table,
              write_mask):
    """Speculative-decoding verify forward: score all C = γ+1 positions of
    ``tokens`` (B, C) = [current token, γ draft proposals] in ONE compiled
    pass, with row b's chunk at absolute positions ``index[b] ..
    index[b]+C-1``.  K/V goes through the block table at per-row traced
    offsets (``write_mask`` (B, C) redirects inactive rows / positions at
    or past the row's limit to the trash page); window rings defer their
    advance into ``pending`` entries that :func:`lm_spec_commit` applies
    once the accept rule picks each row's accepted prefix.  Recurrent
    layers replay the chunk through the decode mixers (:func:`_replay`).
    Returns (logits (B, C, V), cache)."""
    B, C = tokens.shape
    index = jnp.broadcast_to(jnp.asarray(index, jnp.int32), (B,))
    pos = index[:, None] + jnp.arange(C)[None, :]              # (B, C)
    x = maybe_shard(_embed_at(params, cfg, tokens, pos), _ROWS)
    positions = _positions(cfg, pos, B)
    mix = Mixers(
        attn=lambda p, cfg, h, s, window: attn.attn_verify_chunk(
            p, cfg, h, s, index, positions, window, block_table=block_table,
            write_mask=write_mask), per_token=True, **_DECODE)
    x, cache, _ = _stack(params, cfg, x, mix, cache)
    return maybe_shard(_head(params, cfg, x), _LOGITS), cache


def lm_spec_commit(cache, index, acc):
    """Resolve a verify forward's deferred per-row advances: commit each
    row's ``acc`` accepted tokens and drop the ``pending`` entries.  Window
    layers commit their deferred ring writes (``attn.spec_ring_commit``);
    recurrent layers index-select checkpoint ``acc`` from their replay's
    (C+1)-deep state ring (entry 0 = pre-round, so ``acc == 0`` — an
    inactive row — is an exact freeze).  Paged pool leaves pass through —
    rejected positions there live beyond the rewound cursor (never
    readable, always rewritten first), so rollback costs them nothing."""
    acc = jnp.asarray(acc, jnp.int32)
    rows = jnp.arange(acc.shape[0])
    out = {}
    for lname, lc in cache.items():
        if isinstance(lc, dict) and "pending" in lc:
            pend = lc["pending"]
            if "states" in pend:
                # Leaves (n_super, C+1, B, ...): out[s, b] = ck[s, acc[b], b].
                out[lname] = jax.tree.map(lambda ck: ck[:, acc, rows],
                                          pend["states"])
            else:
                k, v = attn.spec_ring_commit(lc["k"], lc["v"], pend["k"],
                                             pend["v"], index, acc)
                out[lname] = {"k": k, "v": v}
        else:
            out[lname] = lc
    return out


# ---------------------------------------------------------------------------
# Decode (single token)
# ---------------------------------------------------------------------------
def _commit_paged_writes(cache):
    """Apply the decode step's deferred pool writes, batched across the
    whole layer scan: each paged layer's attention deferred its one-token
    K/V commit (``pending``: values + physical page/offset, stacked over
    the n_super scan axis by the scan's ys), so the replicated pool sees
    ONE scatter per leaf per step instead of one collective inside every
    scan iteration — the difference between O(1) and O(layers) collective
    launches per generated token on a data-parallel mesh."""
    out = {}
    for lname, lc in cache.items():
        if isinstance(lc, dict) and "pending" in lc:
            pend = lc["pending"]
            if "latent" in pend:        # MLA: one compressed row per token
                sup = jnp.arange(lc["latent_pages"].shape[0])[:, None]
                new_l = {
                    "latent_pages": lc["latent_pages"].at[
                        sup, pend["page"], pend["off"]].set(pend["latent"])}
                if "latent_scale" in pend:   # quantized: scales commit with
                    new_l["latent_scales"] = lc["latent_scales"].at[
                        sup, pend["page"], pend["off"]].set(
                            pend["latent_scale"])
                out[lname] = new_l
                continue
            sup = jnp.arange(lc["k_pages"].shape[0])[:, None]   # (n_super, 1)
            new_l = {
                "k_pages": lc["k_pages"].at[sup, pend["page"],
                                            pend["off"]].set(pend["k"]),
                "v_pages": lc["v_pages"].at[sup, pend["page"],
                                            pend["off"]].set(pend["v"])}
            if "k_scale" in pend:       # quantized: one scatter per scale leaf
                new_l["k_scales"] = lc["k_scales"].at[
                    sup, pend["page"], pend["off"]].set(pend["k_scale"])
                new_l["v_scales"] = lc["v_scales"].at[
                    sup, pend["page"], pend["off"]].set(pend["v_scale"])
            out[lname] = new_l
        else:
            out[lname] = lc
    return out


def lm_decode_step(params, cfg: ModelConfig, tokens, cache, index,
                   positions=None, block_table=None, write_mask=None):
    """tokens: (B, 1) -> (logits (B, 1, V), new_cache).  `index` (B,) int32 is
    the number of tokens already in each row's cache (the absolute position
    of that row's new token); a scalar broadcasts for uniform batches.  Rows
    are fully independent — every row embeds, attends, and writes its cache
    at its own cursor — which is what lets a continuous-batching scheduler
    decode requests at unrelated positions in one compiled step.

    With a paged cache (``lm_init_paged_cache``) the full-attention layers
    read/write the shared pool through ``block_table`` (B, NB); rows with
    ``write_mask == False`` have their pool writes redirected to the trash
    page (the contiguous freeze-select equivalent)."""
    B = tokens.shape[0]
    index = jnp.broadcast_to(jnp.asarray(index, jnp.int32), (B,))
    x = _embed_at(params, cfg, tokens, index[:, None])
    if positions is None:
        positions = _positions(cfg, index[:, None], B)

    def attn_mix(p, cfg, h, s, window):
        if "k_pages" in s or "latent_pages" in s:
            return attn.attn_decode_paged(p, cfg, h, s, block_table, index,
                                          positions, write_mask=write_mask)
        return attn.attn_decode(p, cfg, h, s, index, positions, window=window)
    x, cache, _ = _stack(params, cfg, x, Mixers(attn=attn_mix, **_DECODE),
                         cache, shard=False)
    return _head(params, cfg, x), _commit_paged_writes(cache)
