"""Muon-NSGD — the paper's main optimizer (§2, §B).

All matrix-shaped leaves are updated with Muon (Newton–Schulz orthogonalized
momentum, scaled by the muP spectral factor sqrt(n_out/n_in) so hyperparameters
transfer across depth/width); every other leaf uses normalized SGD, with a
*single* learning rate for both — exactly the paper's Muon-NSGD.

Stacked super-block leaves (leading n_super axis from the layer scan) are
orthogonalized per-layer via vmap over the leading axes (one layer at a time
on a multi-device mesh), so progressive depth expansion leaves optimizer
semantics unchanged.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import OptimizerConfig
from repro.optim.base import Optimizer, clip_by_global_norm


# Leaf names that are *not* semantic matrices even when >=2-D (stacked norm
# scales, per-channel SSM params, token-shift factors, position tables, ...):
# these take NSGD, everything matrix-shaped takes Muon (paper §2).
# (token-shift mu subkeys r/k/v/g/w are matched via their parent dict name
# below, NOT listed here — a top-level matrix that happens to be named "w"
# must still get Muon.)
NSGD_NAMES = frozenset({
    "scale", "bias", "conv_b", "dt_bias", "A_log", "D", "u", "w_base",
    "conv_w", "pos_embed", "enc_pos",
})


def _key_name(entry) -> str:
    return str(getattr(entry, "key", getattr(entry, "name", entry)))


def _path_names(path):
    return [_key_name(p) for p in path]


def _is_matrix(path, x: jax.Array) -> bool:
    names = _path_names(path)
    if names and (names[-1] in NSGD_NAMES or
                  (len(names) >= 2 and names[-2] in ("mu", "cm_mu"))):
        return False
    return x.ndim >= 2 and x.shape[-1] > 1 and x.shape[-2] > 1


def _stacked(path) -> bool:
    names = _path_names(path)
    return bool(names) and names[0] in ("blocks", "enc_blocks")


def orthogonalize(m: jax.Array, steps: int = 5) -> jax.Array:
    """Newton–Schulz quintic iteration (Muon).  Orthogonalizes the trailing
    two dims; leading dims (layer stack, experts) are vmapped.

    Routes through the Pallas kernel on TPU (repro.kernels.newton_schulz);
    on a multi-device mesh every device orthogonalizes the whole
    (gathered) matrix, since the kernel cannot be partitioned.  There a
    stack is orthogonalized one matrix at a time (``lax.map``), so only one
    gathered matrix and its result are live: a whole gathered stack of
    3072 x 12288 layers would not fit beside the sharded state.
    """
    from repro.kernels.newton_schulz import ops as ns_ops
    from repro.models import common

    def run(m):
        x = m.reshape((-1,) + m.shape[-2:])
        y = jax.vmap(lambda a: ns_ops.newton_schulz(a, steps=steps))(x)
        return y.reshape(m.shape)

    if jax.default_backend() == "tpu":
        run = common.kernel_shard_map(run, (P(),), P())
    mesh = common.get_active_mesh()
    if mesh is not None and mesh.size > 1 and m.ndim > 2:
        x = m.reshape((-1,) + m.shape[-2:])
        return jax.lax.map(run, x).reshape(m.shape)
    return run(m)


def muon_nsgd(cfg: OptimizerConfig) -> Optimizer:
    beta = cfg.momentum
    wd = cfg.weight_decay

    def init(params):
        return {"step": jnp.zeros((), jnp.int32),
                "m": jax.tree.map(lambda p: jnp.zeros_like(p), params)}

    def update(grads, state, params, lr):
        grads = clip_by_global_norm(grads, cfg.grad_clip)
        m_new = jax.tree.map(lambda m, g: beta * m + g.astype(m.dtype),
                             state["m"], grads)

        def one(path, p, m):
            if _is_matrix(path, p):
                o = orthogonalize(m.astype(jnp.float32), cfg.ns_steps)
                if cfg.mup:
                    n_in, n_out = p.shape[-2], p.shape[-1]
                    o = o * jnp.sqrt(jnp.asarray(max(n_out, n_in) / n_in,
                                                 jnp.float32))
                upd = o
            else:
                mf = m.astype(jnp.float32)
                if _stacked(path) and mf.ndim > 1:
                    # per-layer normalization: depth expansion must not dilute
                    # each layer's NSGD step (hyperparameter transfer).
                    flat = mf.reshape(mf.shape[0], -1)
                    norm = jnp.linalg.norm(flat, axis=1) + 1e-9
                    upd = (flat / norm[:, None]).reshape(mf.shape)
                else:
                    upd = mf / (jnp.linalg.norm(mf.reshape(-1)) + 1e-9)
            return ((1.0 - lr * wd) * p.astype(jnp.float32)
                    - lr * upd).astype(p.dtype)

        new_params = jax.tree_util.tree_map_with_path(one, params, m_new)
        return new_params, {"step": state["step"] + 1, "m": m_new}

    return Optimizer("muon_nsgd", init, update)
