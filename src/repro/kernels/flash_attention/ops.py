"""Public flash-attention entry point used by the model zoo.

TPU backend -> the Pallas kernel (forward and backward), run per shard on
a multi-device mesh; otherwise the exact jnp path (naive for short
sequences, blocked online-softmax beyond) so CPU tests and dry-run
lowering stay memory-bounded.
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

from repro.kernels.flash_attention import ref
from repro.kernels.flash_attention.kernel import flash_attention_tpu
from repro.models import common


def flash_attention_sharded(q, k, v, *, causal=True, window=0,
                            logit_softcap=0.0, interpret=False):
    """The Pallas kernel over the engine's mesh: batch over the data axes,
    heads over 'model' where both head counts divide (attention is
    independent per row and per head, so the shards need no exchange)."""
    mesh = common.get_active_mesh()
    spec = P()
    if mesh is not None and mesh.size > 1:
        batch = common.mesh_axes_dividing(mesh, q.shape[0], ("pod", "data"))
        m = mesh.shape.get("model", 1)
        heads = ("model" if m > 1 and q.shape[2] % m == 0
                 and k.shape[2] % m == 0 else None)
        spec = P(batch, None, heads, None)

    def run(q, k, v):
        return flash_attention_tpu(q, k, v, causal=causal, window=window,
                                   logit_softcap=logit_softcap,
                                   interpret=interpret)

    return common.kernel_shard_map(run, (spec,) * 3, spec)(q, k, v)


def flash_attention(q, k, v, *, causal=True, window=0, logit_softcap=0.0):
    """q: (B,Sq,H,hd); k,v: (B,Sk,KV,hd) -> (B,Sq,H,hd)."""
    if jax.default_backend() == "tpu":
        return flash_attention_sharded(q, k, v, causal=causal, window=window,
                                       logit_softcap=logit_softcap)
    if q.shape[1] <= 256:
        return ref.naive_attention(q, k, v, causal=causal, window=window,
                                   logit_softcap=logit_softcap)
    return ref.blocked_attention(q, k, v, causal=causal, window=window,
                                 logit_softcap=logit_softcap)
