"""Plain GPT-2 in jax.numpy: weights, forward, loss, Muon-NSGD, schedule.

The yardstick that decides ``correct`` for the GPT-2 cells.  It imports
nothing of the program.  It follows the configuration as the benchmark
runs it (``configs/<name>.json``): pre-norm decoder layers with LayerNorm,
causal multi-head attention without biases, a GELU (tanh) MLP, learned
absolute positions and a head tied to the token embedding; Muon with the
quintic Newton–Schulz iteration on matrices and normalized SGD on the rest
(the source paper's Muon-NSGD); a warmup-stable-decay schedule.

Weights are stored stacked over layers (``blocks/layer0/...`` with a
leading layer axis), which is also the layout the program takes, so the
benchmark makes one set of weights from the seed and hands the same values
to both.  Layers are applied one by one, never through the program's scan.

Everything runs at ``jax.default_matmul_precision('highest')`` in float32
unless a lower ``dtype`` is asked for (the low-precision control).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

NS_COEFFS = (3.4445, -4.7750, 2.0315)
NSGD_NAMES = ("pos_embed", "scale", "bias")


# -- weights -----------------------------------------------------------------

def init(key, m: dict, layers: int, dtype=jnp.float32) -> dict:
    """Seeded weights: normal with std 0.02 (token embedding), 0.01
    (positions) and 1/sqrt(fan_in) (projections); LayerNorms at 1 and 0."""
    d, f, V = m["d_model"], m["d_ff"], m["vocab_size"]
    q = m["num_heads"] * m["head_dim"]
    kv = m["num_kv_heads"] * m["head_dim"]
    k = jax.random.split(key, 8)

    def normal(key, shape, std):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)

    def dense(key, n_in, n_out):
        return normal(key, (layers, n_in, n_out), 1.0 / math.sqrt(n_in))

    def norm():
        return {"scale": jnp.ones((layers, d), dtype),
                "bias": jnp.zeros((layers, d), dtype)}

    params = {"embed": normal(k[0], (V, d), 0.02),
              "pos_embed": normal(k[1], (m["max_seq_len"], d), 0.01),
              "final_norm": {"scale": jnp.ones((d,), dtype),
                             "bias": jnp.zeros((d,), dtype)}}
    if layers:
        params["blocks"] = {"layer0": {
            "ln1": norm(),
            "attn": {"wq": dense(k[2], d, q), "wk": dense(k[3], d, kv),
                     "wv": dense(k[4], d, kv), "wo": dense(k[5], q, d)},
            "ln2": norm(),
            "mlp": {"w_up": dense(k[6], d, f), "w_down": dense(k[7], f, d)}}}
    return params


def num_layers(params) -> int:
    if "blocks" not in params:
        return 0
    return params["blocks"]["layer0"]["attn"]["wq"].shape[0]


# -- forward -----------------------------------------------------------------

def _layer_norm(p, x, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _attention(p, x, m):
    B, S, _ = x.shape
    H, KV, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    k = (x @ p["wk"]).reshape(B, S, KV, hd)
    v = (x @ p["wv"]).reshape(B, S, KV, hd)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, jnp.asarray(-1e30, s.dtype))
    w = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(x.dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(B, S, H * hd)
    return o @ p["wo"]


def logits(params, m: dict, tokens, eps: float):
    """(B, S) int tokens -> (B, S, V) logits, in the weights' dtype."""
    S = tokens.shape[1]
    x = params["embed"][tokens] + params["pos_embed"][:S]
    blocks = params.get("blocks", {}).get("layer0")
    for i in range(num_layers(params)):
        lp = jax.tree.map(lambda a: a[i], blocks)
        x = x + _attention(lp["attn"], _layer_norm(lp["ln1"], x, eps), m)
        h = _layer_norm(lp["ln2"], x, eps)
        x = x + jax.nn.gelu(h @ lp["mlp"]["w_up"], approximate=True) \
            @ lp["mlp"]["w_down"]
    x = _layer_norm(params["final_norm"], x, eps)
    return x @ params["embed"].T


def loss(params, m: dict, tokens, labels, eps: float):
    z = logits(params, m, tokens, eps).astype(jnp.float32)
    lse = jax.nn.logsumexp(z, axis=-1)
    gold = jnp.take_along_axis(z, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


@functools.lru_cache(maxsize=None)
def _value_and_grad(model_items, eps):
    m = dict(model_items)
    return jax.jit(jax.value_and_grad(
        lambda p, t, l: loss(p, m, t, l, eps)))


def loss_and_grads(params, m: dict, tokens, labels, eps: float, rows: int):
    """Mean loss over the batch and its gradient, computed ``rows`` rows at
    a time so that the activations of one block fit beside the program's
    leftovers; blocks are weighted by their share of the batch."""
    B = tokens.shape[0]
    vg = _value_and_grad(tuple(sorted(m.items())), eps)
    total, grads = 0.0, None
    for lo in range(0, B, rows):
        hi = min(B, lo + rows)
        w = (hi - lo) / B
        lv, g = vg(params, tokens[lo:hi], labels[lo:hi])
        total = total + w * lv
        g = jax.tree.map(lambda x: x * jnp.asarray(w, x.dtype), g)
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    return total, grads


# -- Muon-NSGD ---------------------------------------------------------------

def newton_schulz(x, steps: int = 5):
    """Quintic Newton–Schulz on one matrix: singular values towards 1."""
    a, b, c = NS_COEFFS
    tall = x.shape[0] > x.shape[1]
    if tall:
        x = x.T
    x = x / (jnp.linalg.norm(x) + 1e-7)
    for _ in range(steps):
        g = x @ x.T
        x = a * x + (b * g + c * (g @ g)) @ x
    return x.T if tall else x


def _names(path):
    return [str(getattr(p, "key", p)) for p in path]


def _is_matrix(path, x) -> bool:
    return (_names(path)[-1] not in NSGD_NAMES and x.ndim >= 2
            and x.shape[-1] > 1 and x.shape[-2] > 1)


def muon_nsgd(params, momentum, grads, lr, opt: dict):
    """One Muon-NSGD update: momentum m <- beta m + g; matrices move by
    NS(m) * sqrt(max(n_out, n_in) / n_in), every layer's matrix on its own;
    other leaves by m / |m| (per layer where stacked); decoupled weight
    decay lr * wd.  Returns (params, momentum)."""
    beta, wd = opt["momentum"], opt["weight_decay"]
    steps = opt["ns_steps"]
    momentum = jax.tree.map(lambda mm, g: beta * mm + g, momentum, grads)

    def one(path, p, mm):
        stacked = _names(path)[0] == "blocks"
        if _is_matrix(path, p):
            lead = mm.reshape((-1,) + mm.shape[-2:])
            o = jax.vmap(lambda a: newton_schulz(a, steps))(lead)
            n_in, n_out = p.shape[-2], p.shape[-1]
            upd = o.reshape(mm.shape) * math.sqrt(max(n_out, n_in) / n_in)
        elif stacked and mm.ndim > 1:
            flat = mm.reshape(mm.shape[0], -1)
            upd = (flat / (jnp.linalg.norm(flat, axis=1, keepdims=True)
                           + 1e-9)).reshape(mm.shape)
        else:
            upd = mm / (jnp.linalg.norm(mm.reshape(-1)) + 1e-9)
        return ((1.0 - lr * wd) * p - lr * upd).astype(p.dtype)

    return jax.tree_util.tree_map_with_path(one, params, momentum), momentum


def wsd_lr(step: int, total: int, sched: dict, peak: float) -> float:
    """Warmup-stable-decay learning rate at ``step`` of ``total``."""
    warm = max(1, int(total * sched["warmup_frac"]))
    decay = max(1, int(total * sched["decay_frac"]))
    stable_end = total - decay
    if step < warm:
        return min(peak * (step + 1) / warm, peak)
    if step < stable_end:
        return peak
    frac = min(max((step - stable_end) / decay, 0.0), 1.0)
    return peak * (1.0 - (1.0 - sched["min_lr_frac"]) * frac)
