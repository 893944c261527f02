"""Shared model building blocks: norms, activations, embeddings, RoPE/M-RoPE,
initializers, and sharding-constraint helpers.

All models are pure-JAX ``init(key, cfg) -> params`` / ``apply(params, ...)``
function pairs over nested-dict pytrees.  No framework dependency.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


# ---------------------------------------------------------------------------
# Sharding-constraint helper (no-op outside a mesh context)
# ---------------------------------------------------------------------------

# Activation-parallel layout (§Perf h3):
#   'tp'   — batch over (pod,data), sequence/heads/ffn over 'model'
#            (Megatron-SP style; default),
#   'fsdp' — batch over (pod,data,model); 'model' never shards activations
#            (pure ZeRO-3: no sequence-parallel boundary collectives).
_ACTIVATION_LAYOUT = "tp"


def set_activation_layout(mode: str):
    global _ACTIVATION_LAYOUT
    assert mode in ("tp", "fsdp")
    globals()["_ACTIVATION_LAYOUT"] = mode


def get_activation_layout() -> str:
    return _ACTIVATION_LAYOUT


def _apply_layout(spec: P) -> P:
    if _ACTIVATION_LAYOUT == "tp":
        return spec
    out = []
    for entry in spec:
        if isinstance(entry, (tuple, list)) and "data" in entry:
            # big axes first: maybe_shard's greedy divisibility check then
            # keeps (data, model) when the batch doesn't divide the full
            # extent (e.g. batch 256 on the 512-chip multi-pod mesh).
            ext = ("data", "model") + tuple(a for a in entry
                                            if a not in ("data", "model"))
            out.append(ext)
        elif entry == "model":
            out.append(None)
        else:
            out.append(entry)
    return P(*out)


# Concrete mesh registered by the train/serve engine.  An abstract-mesh
# context (``jax.sharding.use_mesh``) wins where one is set; otherwise the
# engine's registration is how activation constraints resolve, so
# maybe_shard is a no-op unless an engine is active.
_ACTIVE_MESH: Optional[jax.sharding.Mesh] = None


def set_active_mesh(mesh: Optional[jax.sharding.Mesh]):
    """Register (or clear, with None) the engine's mesh for maybe_shard."""
    global _ACTIVE_MESH
    _ACTIVE_MESH = mesh


def get_active_mesh() -> Optional[jax.sharding.Mesh]:
    return _ACTIVE_MESH


def _current_mesh():
    mesh = jax.sharding.get_abstract_mesh()
    return _ACTIVE_MESH if mesh.empty else mesh


def maybe_shard(x: jax.Array, spec: P) -> jax.Array:
    """Apply a sharding constraint when tracing under a mesh; no-op otherwise."""
    try:
        spec = _apply_layout(spec)
        mesh = _current_mesh()
        if mesh is None:
            return x
        # Drop axes the current mesh doesn't have (e.g. 'pod' on single-pod)
        # and axes whose size doesn't divide the dimension (e.g. 8 KV heads
        # on a 16-way 'model' axis) — replicate those dims instead.
        clean = []
        for i, entry in enumerate(spec):
            dim = x.shape[i] if i < x.ndim else 1
            if entry is None:
                clean.append(None)
                continue
            axes = entry if isinstance(entry, (tuple, list)) else (entry,)
            clean.append(mesh_axes_dividing(mesh, dim, axes))
        clean = clean[:x.ndim]
        if isinstance(mesh, jax.sharding.Mesh):     # concrete (engine) mesh
            return jax.lax.with_sharding_constraint(
                x, jax.sharding.NamedSharding(mesh, P(*clean)))
        return jax.lax.with_sharding_constraint(x, P(*clean))
    except Exception:
        return x


def mesh_axes_dividing(mesh, dim: int, axes) -> Optional[tuple]:
    """The prefix-greedy subset of ``axes`` present in ``mesh`` whose size
    product divides ``dim`` (None when empty) — a PartitionSpec entry."""
    sizes = dict(mesh.shape)
    kept, prod = [], 1
    for a in axes:
        if a in sizes and dim % (prod * sizes[a]) == 0:
            kept.append(a)
            prod *= sizes[a]
    return tuple(kept) or None


def kernel_shard_map(fn, in_specs, out_specs):
    """Wrap a Pallas kernel call for the engine's mesh.  The TPU compiler
    cannot partition a Pallas kernel, so on a multi-device mesh the kernel
    runs per shard under ``shard_map``; on one device (or outside an
    engine) ``fn`` is returned unchanged."""
    mesh = _ACTIVE_MESH
    if mesh is None or mesh.size == 1:
        return fn
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


BATCH_SPEC = P(("pod", "data"))           # activations: batch over DP axes
SEQ_MODEL = P(("pod", "data"), None, "model")  # (B, S, D_model-sharded)


# ---------------------------------------------------------------------------
# Initializers (muP-friendly)
# ---------------------------------------------------------------------------

def dense_init(key, in_dim: int, out_dim: int, dtype=jnp.float32,
               scale: float = 1.0) -> jax.Array:
    """muP/spectral-consistent init: std = scale / sqrt(in_dim).

    Satisfies the spectral condition ||W||_* ~ sqrt(out/in) of §3.2 up to
    constants, preserving per-element activation scale across layers.
    """
    std = scale / math.sqrt(in_dim)
    return (jax.random.normal(key, (in_dim, out_dim)) * std).astype(dtype)


def embed_init(key, vocab: int, dim: int, dtype=jnp.float32) -> jax.Array:
    return (jax.random.normal(key, (vocab, dim)) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def norm_init(d: int, kind: str):
    if kind == "layernorm":
        return {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))}
    return {"scale": jnp.ones((d,))}


def apply_norm(p, x: jax.Array, kind: str, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    if kind == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(ms + eps) * p["scale"]
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------

def activate(x: jax.Array, kind: str) -> jax.Array:
    if kind == "gelu":
        return jax.nn.gelu(x)
    if kind == "silu":
        return jax.nn.silu(x)
    raise ValueError(kind)


def softcap(x: jax.Array, cap: float) -> jax.Array:
    """Gemma2 logit soft-capping: cap * tanh(x / cap)."""
    if cap <= 0.0:
        return x
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# Rotary position embeddings (RoPE and 3D M-RoPE)
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    sin = sin[..., :, None, :]                          # broadcast over heads
    cos = cos[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, positions_3d: jax.Array, theta: float,
                sections=(16, 24, 24)) -> jax.Array:
    """Qwen2-VL multimodal RoPE.

    positions_3d: (3, ..., S) temporal/height/width position ids.  The rotary
    frequency bands are partitioned into `sections` (by half-dim), each band
    rotated by its own position component.
    """
    hd = x.shape[-1]
    half = hd // 2
    secs = list(sections)
    if sum(secs) != half:  # rescale sections to this head_dim
        tot = sum(secs)
        secs = [s * half // tot for s in secs]
        secs[0] += half - sum(secs)
    freqs = rope_freqs(hd, theta)                       # (half,)
    # Build per-band position array: (..., S, half)
    parts = []
    start = 0
    for i, s in enumerate(secs):
        pos = positions_3d[i]                           # (..., S)
        parts.append(jnp.broadcast_to(pos[..., None], pos.shape + (s,)))
        start += s
    pos_bands = jnp.concatenate(parts, axis=-1).astype(jnp.float32)
    angles = pos_bands * freqs                          # (..., S, half)
    sin = jnp.sin(angles)[..., :, None, :]
    cos = jnp.cos(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(max_len: int, dim: int) -> jax.Array:
    """Absolute sinusoidal table (whisper encoder)."""
    pos = jnp.arange(max_len, dtype=jnp.float32)[:, None]
    inv = jnp.exp(-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim * math.log(10000.0))
    ang = pos * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------

def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: Optional[jax.Array] = None,
                  final_softcap: float = 0.0) -> jax.Array:
    """Mean next-token cross entropy. logits (B,S,V), labels (B,S)."""
    logits = softcap(logits.astype(jnp.float32), final_softcap)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
