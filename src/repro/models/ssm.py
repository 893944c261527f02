"""State-space / linear-recurrence blocks: Mamba (S6) and RWKV6 (Finch).

The sequence recurrences route through Pallas chunked-scan kernels on TPU
(``repro.kernels.mamba_scan`` / ``repro.kernels.rwkv6``) with pure-jnp
references elsewhere.  Decode maintains O(1) recurrent state — no KV cache.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import dense_init, norm_init, apply_norm


# ===========================================================================
# Mamba (selective SSM)
# ===========================================================================

def mamba_dims(cfg: ModelConfig):
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    dt_rank = s.dt_rank or -(-cfg.d_model // 16)
    return d_inner, dt_rank, s.d_state, s.d_conv


def mamba_init(key, cfg: ModelConfig, dtype=jnp.float32):
    D = cfg.d_model
    d_inner, dt_rank, d_state, d_conv = mamba_dims(cfg)
    ks = jax.random.split(key, 7)
    A = jnp.broadcast_to(jnp.arange(1, d_state + 1, dtype=jnp.float32),
                         (d_inner, d_state))
    return {
        "in_proj": dense_init(ks[0], D, 2 * d_inner, dtype),
        "conv_w": (jax.random.normal(ks[1], (d_conv, d_inner)) /
                   math.sqrt(d_conv)).astype(dtype),
        "conv_b": jnp.zeros((d_inner,), dtype),
        "x_proj": dense_init(ks[2], d_inner, dt_rank + 2 * d_state, dtype),
        "dt_proj": dense_init(ks[3], dt_rank, d_inner, dtype, scale=dt_rank ** 0.5),
        "dt_bias": (jnp.log(jnp.expm1(
            jnp.clip(jnp.exp(jax.random.uniform(ks[4], (d_inner,))
                             * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3)),
                     1e-4, None)))).astype(dtype),
        "A_log": jnp.log(A).astype(dtype),
        "D": jnp.ones((d_inner,), dtype),
        "out_proj": dense_init(ks[5], d_inner, D, dtype),
    }


def _mamba_project(p, cfg, x):
    """Shared pre-scan projections. x: (B, S, D)."""
    d_inner, dt_rank, d_state, _ = mamba_dims(cfg)
    xz = x @ p["in_proj"]
    xs, z = jnp.split(xz, 2, axis=-1)                      # (B,S,d_inner) each
    return xs, z


def _mamba_ssm_params(p, cfg, u):
    """u: (B,S,d_inner) post-conv activations -> (dt, B_mat, C_mat)."""
    d_inner, dt_rank, d_state, _ = mamba_dims(cfg)
    xdbc = u @ p["x_proj"]
    dt, Bm, Cm = jnp.split(xdbc, [dt_rank, dt_rank + d_state], axis=-1)
    dt = jax.nn.softplus(dt @ p["dt_proj"] + p["dt_bias"])  # (B,S,d_inner)
    return dt, Bm, Cm


def mamba_apply(p, cfg: ModelConfig, x: jax.Array, state=None):
    """Full-sequence Mamba block. x: (B, S, D) -> (B, S, D).

    With ``state`` (serve prefill) the incoming conv/ssm state replaces the
    zero left-context, the exact state-returning scan is used, and the
    return becomes ``(y, new_state)`` — the state a token-by-token decode of
    the same sequence would leave.  One code path: the prefill handoff
    cannot drift from the train forward."""
    from repro.kernels.mamba_scan import ops as scan_ops
    B, S, D = x.shape
    d_inner, dt_rank, d_state, d_conv = mamba_dims(cfg)
    xs, z = _mamba_project(p, cfg, x)
    # Depthwise causal conv over time (left context: zeros, or the state's).
    if state is None:
        ctx = jnp.pad(xs, ((0, 0), (d_conv - 1, 0), (0, 0)))
    else:
        ctx = jnp.concatenate([state["conv"].astype(xs.dtype), xs], axis=1)
    u = sum(ctx[:, i:i + S, :] * p["conv_w"][i] for i in range(d_conv))
    u = jax.nn.silu(u + p["conv_b"])
    dt, Bm, Cm = _mamba_ssm_params(p, cfg, u)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))            # (d_inner, d_state)
    if state is None:
        y = scan_ops.selective_scan(u, dt, A, Bm, Cm, p["D"])
        return (y * jax.nn.silu(z)) @ p["out_proj"]
    y, h = scan_ops.selective_scan_with_state(u, dt, A, Bm, Cm, p["D"],
                                              h0=state["ssm"])
    new_state = {"conv": ctx[:, S:].astype(state["conv"].dtype), "ssm": h}
    return (y * jax.nn.silu(z)) @ p["out_proj"], new_state


def mamba_prefill(p, cfg: ModelConfig, x: jax.Array, state) -> Tuple[jax.Array, dict]:
    """Prefill = ``mamba_apply`` advancing the decode state; see there."""
    return mamba_apply(p, cfg, x, state=state)


def mamba_init_state(cfg: ModelConfig, batch: int, dtype=jnp.float32):
    d_inner, dt_rank, d_state, d_conv = mamba_dims(cfg)
    return {"conv": jnp.zeros((batch, d_conv - 1, d_inner), dtype),
            "ssm": jnp.zeros((batch, d_inner, d_state), jnp.float32)}


def mamba_decode(p, cfg: ModelConfig, x: jax.Array, state) -> Tuple[jax.Array, dict]:
    """Single-token step. x: (B, 1, D) -> (B, 1, D), carrying O(1) state.

    Contract (continuous batching): the conv/ssm state advance is strictly
    per-row — row b's new state depends only on row b's input and old state —
    so the serve decode step can freeze terminated rows with a per-row
    select and a scheduler can scatter a freshly prefilled row's state into
    any batch slot without touching live rows.

    Contract (speculative decoding): this function is the single source of
    truth for the recurrent step.  ``transformer._replay`` replays it, as
    the mixer of ``transformer._block``, token-by-token under ``lax.scan``
    from the pre-round state (collecting per-step state checkpoints for
    the rollback index-select), and the draft loop's (γ+2)-deep
    checkpoint ring snapshots its outputs — so spec-vs-solo byte parity
    holds because both paths run these exact ops."""
    B = x.shape[0]
    d_inner, dt_rank, d_state, d_conv = mamba_dims(cfg)
    xs, z = _mamba_project(p, cfg, x)                      # (B,1,d_inner)
    conv_buf = jnp.concatenate([state["conv"], xs], axis=1)  # (B,d_conv,d_inner)
    u = jnp.einsum("bcd,cd->bd", conv_buf, p["conv_w"]) + p["conv_b"]
    u = jax.nn.silu(u)[:, None, :]                         # (B,1,d_inner)
    dt, Bm, Cm = _mamba_ssm_params(p, cfg, u)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    dA = jnp.exp(dt[:, 0, :, None].astype(jnp.float32) * A)  # (B,d_inner,d_state)
    dBx = (dt[:, 0, :, None] * Bm[:, 0, None, :]).astype(jnp.float32) \
        * u[:, 0, :, None].astype(jnp.float32)
    h = state["ssm"] * dA + dBx                            # (B,d_inner,d_state)
    y = jnp.einsum("bds,bs->bd", h, Cm[:, 0].astype(jnp.float32))
    y = (y + p["D"] * u[:, 0]).astype(x.dtype)[:, None, :]
    y = y * jax.nn.silu(z)
    out = y @ p["out_proj"]
    return out, {"conv": conv_buf[:, 1:], "ssm": h}


# ===========================================================================
# RWKV6 (Finch): data-dependent decay linear attention
# ===========================================================================

def rwkv_dims(cfg: ModelConfig):
    hd = cfg.ssm.head_dim if cfg.ssm else 64
    return cfg.d_model // hd, hd


def rwkv_init(key, cfg: ModelConfig, dtype=jnp.float32):
    D = cfg.d_model
    H, hd = rwkv_dims(cfg)
    lora = max(32, D // 64)
    ks = jax.random.split(key, 12)
    p = {
        # token-shift interpolation factors per stream
        "mu": {n: (0.5 * jnp.ones((D,), dtype)) for n in ("r", "k", "v", "g", "w")},
        "w_r": dense_init(ks[0], D, D, dtype),
        "w_k": dense_init(ks[1], D, D, dtype),
        "w_v": dense_init(ks[2], D, D, dtype),
        "w_g": dense_init(ks[3], D, D, dtype),
        # data-dependent decay: w = base + tanh(x Wa) Wb  (low-rank, Finch)
        "w_base": (-6.0 + 5.0 * (jnp.arange(D) / max(D - 1, 1)) ** 0.7).astype(dtype),
        "w_a": dense_init(ks[4], D, lora, dtype),
        "w_b": dense_init(ks[5], lora, D, dtype, scale=0.1),
        "u": (jax.random.normal(ks[6], (H, hd)) * 0.1).astype(dtype),
        "w_o": dense_init(ks[7], D, D, dtype),
        "ln_x": norm_init(D, "layernorm"),  # group-norm over heads approximated
        # channel mixing
        "cm_mu": {n: (0.5 * jnp.ones((D,), dtype)) for n in ("r", "k")},
        "cm_r": dense_init(ks[8], D, D, dtype),
        "cm_k": dense_init(ks[9], D, cfg.d_ff, dtype),
        "cm_v": dense_init(ks[10], cfg.d_ff, D, dtype),
    }
    return p


def _token_shift(x, x_prev_last=None):
    """Shift sequence right by one.  x: (B,S,D)."""
    if x_prev_last is None:
        prev = jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    else:
        prev = jnp.concatenate([x_prev_last[:, None, :], x[:, :-1]], axis=1)
    return prev


def _rwkv_streams(p, x, prev):
    def lerp(mu):
        return x + (prev - x) * mu
    r = lerp(p["mu"]["r"]) @ p["w_r"]
    k = lerp(p["mu"]["k"]) @ p["w_k"]
    v = lerp(p["mu"]["v"]) @ p["w_v"]
    g = lerp(p["mu"]["g"]) @ p["w_g"]
    xw = lerp(p["mu"]["w"])
    w = p["w_base"] + jnp.tanh(xw @ p["w_a"]) @ p["w_b"]
    w = jnp.exp(-jnp.exp(w.astype(jnp.float32)))           # decay in (0,1)
    return r, k, v, g, w


def rwkv_time_mix(p, cfg: ModelConfig, x: jax.Array, state=None,
                  x_prev=None, return_state: bool = False):
    """x: (B,S,D) -> (B,S,D).  state: WKV matrix (B,H,hd,hd) or None
    (zeros); x_prev: (B,D) last pre-mix input for token shift (serve
    prefill continuation).  ``return_state=True`` also returns the final
    WKV state — one code path for train and prefill."""
    from repro.kernels.rwkv6 import ops as rwkv_ops
    B, S, D = x.shape
    H, hd = rwkv_dims(cfg)
    prev = _token_shift(x, None if x_prev is None
                        else x_prev.astype(x.dtype))
    r, k, v, g, w = _rwkv_streams(p, x, prev)
    rh = r.reshape(B, S, H, hd)
    kh = k.reshape(B, S, H, hd)
    vh = v.reshape(B, S, H, hd)
    wh = w.reshape(B, S, H, hd)
    if state is None:
        state = jnp.zeros((B, H, hd, hd), jnp.float32)
    y, state_f = rwkv_ops.wkv(rh, kh, vh, wh, p["u"], state)
    y = y.reshape(B, S, D)
    y = apply_norm(p["ln_x"], y, "layernorm")
    y = y * jax.nn.silu(g)
    out = y @ p["w_o"]
    return (out, state_f) if return_state else out


def rwkv_channel_mix(p, cfg: ModelConfig, x: jax.Array,
                     x_prev=None) -> jax.Array:
    prev = _token_shift(x, None if x_prev is None
                        else x_prev.astype(x.dtype))
    xr = x + (prev - x) * p["cm_mu"]["r"]
    xk = x + (prev - x) * p["cm_mu"]["k"]
    r = jax.nn.sigmoid(xr @ p["cm_r"])
    k = jnp.square(jax.nn.relu(xk @ p["cm_k"]))
    return r * (k @ p["cm_v"])


def rwkv_time_mix_prefill(p, cfg: ModelConfig, x: jax.Array,
                          state) -> Tuple[jax.Array, dict]:
    """Prefill = ``rwkv_time_mix`` seeded from and advancing the decode
    state dict (token shift from tm_x, WKV recurrence from wkv)."""
    y, state_f = rwkv_time_mix(p, cfg, x, state=state["wkv"],
                               x_prev=state["tm_x"], return_state=True)
    return y, {**state, "tm_x": x[:, -1].astype(state["tm_x"].dtype),
               "wkv": state_f}


def rwkv_channel_mix_prefill(p, cfg: ModelConfig, x: jax.Array,
                             state) -> Tuple[jax.Array, dict]:
    """Prefill = ``rwkv_channel_mix`` advancing the token-shift state."""
    out = rwkv_channel_mix(p, cfg, x, x_prev=state["cm_x"])
    return out, {**state, "cm_x": x[:, -1].astype(state["cm_x"].dtype)}


def rwkv_init_state(cfg: ModelConfig, batch: int):
    H, hd = rwkv_dims(cfg)
    return {"tm_x": jnp.zeros((batch, cfg.d_model)),
            "cm_x": jnp.zeros((batch, cfg.d_model)),
            "wkv": jnp.zeros((batch, H, hd, hd), jnp.float32)}


def rwkv_decode(p, cfg: ModelConfig, x: jax.Array, state) -> Tuple[jax.Array, dict]:
    """Single-token RWKV layer step (time mix only; channel mix separate).
    x: (B,1,D).  Same per-row contract as ``mamba_decode``: the tm_x/wkv
    state advance never mixes rows, so per-row freeze/scatter is exact —
    and the same spec-decode contract: verify replays this step (plus
    ``rwkv_channel_mix_decode``) under ``lax.scan``, checkpointing states
    per token for rollback."""
    B, _, D = x.shape
    H, hd = rwkv_dims(cfg)
    prev = state["tm_x"][:, None, :].astype(x.dtype)
    r, k, v, g, w = _rwkv_streams(p, x, prev)
    rh = r.reshape(B, H, hd).astype(jnp.float32)
    kh = k.reshape(B, H, hd).astype(jnp.float32)
    vh = v.reshape(B, H, hd).astype(jnp.float32)
    wh = w.reshape(B, H, hd)
    S = state["wkv"]                                       # (B,H,hd,hd) k x v
    kv = kh[..., :, None] * vh[..., None, :]               # (B,H,hd,hd)
    y = jnp.einsum("bhk,bhkv->bhv", rh, S + p["u"].astype(jnp.float32)[None, :, :, None] * kv)
    S_new = S * wh[..., :, None] + kv
    y = y.reshape(B, 1, D).astype(x.dtype)
    y = apply_norm(p["ln_x"], y, "layernorm")
    y = y * jax.nn.silu(g)
    out = (y @ p["w_o"]).astype(x.dtype)
    return out, {**state, "tm_x": x[:, 0].astype(state["tm_x"].dtype),
                 "wkv": S_new}


def rwkv_channel_mix_decode(p, cfg, x, state):
    prev = state["cm_x"][:, None, :].astype(x.dtype)
    xr = x + (prev - x) * p["cm_mu"]["r"]
    xk = x + (prev - x) * p["cm_mu"]["k"]
    r = jax.nn.sigmoid(xr @ p["cm_r"])
    k = jnp.square(jax.nn.relu(xk @ p["cm_k"]))
    return (r * (k @ p["cm_v"])).astype(x.dtype), \
        {**state, "cm_x": x[:, 0].astype(state["cm_x"].dtype)}
