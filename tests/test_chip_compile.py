"""Compile-only tests: the Pallas kernels of the GPT-2 train and serve path,
at ``gpt2-12l`` widths, compiled by the TPU compiler for a described
``v5e:2x2`` chip (nothing runs; no chip is needed).

Interpret-mode tests (``test_kernels.py``) cannot see what the chip's
compiler refuses: block shapes that break the (8, 128) tiling, kernels
that need more VMEM than they may use, a kernel without a backward pass.
These can.  The kernel functions are called with ``interpret=False``
directly, because the ``ops.py`` dispatchers read ``jax.default_backend()``,
which is the CPU here.

The topology is described inside a module fixture (never at import), so
every pytest-xdist worker collects the same tests and only the worker that
runs this file loads the TPU compiler.  The persistent compilation cache is
off around these compiles: an entry written for a described chip cannot be
read back without one.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro import configs as cfglib
from repro.kernels.flash_attention.kernel import _plan, flash_attention_tpu
from repro.kernels.newton_schulz import kernel as ns_kernel
from repro.kernels.newton_schulz import ops as ns_ops
from repro.kernels.paged_attention.kernel import paged_attention_tpu
from repro.models import registry
from repro.optim import muon

GPT2 = cfglib.get_config("gpt2-12l")
B, S = 8, 1024


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, *shapes):
    """Lower and compile ``fn`` for the described chip; returns the HLO text
    of the compiled program."""
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _struct(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _qkv(one_chip, dtype=jnp.float32):
    H, hd = GPT2.num_heads, GPT2.head_dim
    return [_struct((B, S, H, hd), dtype, one_chip) for _ in range(3)]


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

def test_flash_forward_compiles(one_chip):
    hlo = _compile(lambda q, k, v: flash_attention_tpu(q, k, v), *_qkv(one_chip))
    assert "tpu_custom_call" in hlo


def test_flash_forward_backward_compiles(one_chip):
    def loss(q, k, v):
        return jnp.sum(flash_attention_tpu(q, k, v) ** 2)

    hlo = _compile(jax.grad(loss, argnums=(0, 1, 2)), *_qkv(one_chip))
    # forward + dq + dk/dv kernels
    assert hlo.count("tpu_custom_call") >= 3


def _model_layout_grad_hlo(one_chip, H, KV, hd):
    """HLO of the forward and backward of attention on q, k, v as the
    model's projections give them, ``(B, S, heads * hd)``."""
    def loss(q, k, v):
        o = flash_attention_tpu(q.reshape(B, S, H, hd),
                                k.reshape(B, S, KV, hd),
                                v.reshape(B, S, KV, hd))
        return jnp.sum(o.reshape(B, S, H * hd) ** 2)

    shapes = [_struct((B, S, n * hd), jnp.float32, one_chip)
              for n in (H, KV, KV)]
    return _compile(jax.grad(loss, argnums=(0, 1, 2)), *shapes)


def _kernel_calls(hlo):
    """{kernel name: [operand and result shapes]} of the Pallas calls."""
    calls = {}
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name = re.search(r"flash_attention_(fwd|dq|dkv)", line).group(0)
        result = line.split(" custom-call(")[0]
        operands = re.search(r"operand_layout_constraints=\{(.*?)\}\}",
                             line).group(1)
        calls[name] = re.findall(r"f32\[[\d,]*\]", result + operands)
    return calls


def _copies(hlo):
    """Result shapes of the copies and transposes in the HLO."""
    return re.findall(r"= (f32\[[\d,]*\])\{[^}]*\} (?:copy|transpose)\(",
                      hlo)


def test_flash_gpt2_step_runs_on_the_model_layout(one_chip):
    """At gpt2-12l widths the kernels read and write the model's (B, S, 768)
    arrays and dense (B, 6, 2, S) statistics: no head-major transposes, no
    (..., S, 1) statistics, no copy of an activation."""
    H, hd = GPT2.num_heads, GPT2.head_dim
    assert _plan((B, S, H, hd), (B, S, H, hd)) == ("lane_dense", 2)
    hlo = _model_layout_grad_hlo(one_chip, H, H, hd)
    calls = _kernel_calls(hlo)
    assert set(calls) == {"flash_attention_fwd", "flash_attention_dq",
                          "flash_attention_dkv"}
    act, stat = f"f32[{B},{S},{H * hd}]", f"f32[{B},{H // 2},2,{S}]"
    for name, shapes in calls.items():
        assert act in shapes, name
        assert set(shapes) <= {act, stat}, (name, shapes)
    assert not any(c in (f"f32[{B},{S},{H},{hd}]", f"f32[{B},{H},{S},{hd}]",
                         act) for c in _copies(hlo)), _copies(hlo)
    assert not re.search(rf"f32\[[\d,]*,{S},1\]", hlo)


def test_gpt2_grad_under_remat_runs_the_forward_kernel_once(one_chip,
                                                            monkeypatch):
    """The gradient of ``gpt2-12l``'s loss at 2 layers under ``remat=
    "nothing"``: the checkpoint keeps the forward kernel's o and lse, so
    the backward recomputes the layer without re-running the kernel."""
    from repro.models import transformer
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = GPT2.with_depth(2)
    params = jax.eval_shape(lambda k: transformer.lm_init(k, cfg),
                            jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: _struct(a.shape, a.dtype, one_chip),
                          params)
    tokens = _struct((B, S), jnp.int32, one_chip)

    def grad(p, tokens, labels):
        return jax.grad(lambda p: transformer.lm_loss(
            p, cfg, tokens, labels, remat="nothing")[0])(p)

    hlo = _compile(grad, params, tokens, tokens)
    assert set(_kernel_calls(hlo)) == {"flash_attention_fwd",
                                       "flash_attention_dq",
                                       "flash_attention_dkv"}
    assert hlo.count('custom_call_target="tpu_custom_call"') == 3


def test_flash_gqa_hd128_compiles_lane_dense(one_chip):
    """starcoder2-3b's attention (24 q heads, 2 kv heads of 128): one head
    per lane block, GQA in the index map."""
    cfg = cfglib.get_config("starcoder2-3b")
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    assert _plan((B, S, H, hd), (B, S, KV, hd)) == ("lane_dense", 1)
    calls = _kernel_calls(_model_layout_grad_hlo(one_chip, H, KV, hd))
    assert f"f32[{B},{S},{KV * hd}]" in calls["flash_attention_fwd"]
    assert f"f32[{B},{S},{H * hd}]" in calls["flash_attention_dkv"]


def test_flash_gqa_hd64_compiles_head_major(one_chip):
    """The paper's llama3-0.3b testbed (16 q heads, 8 kv heads of 64): GQA
    at hd 64 keeps the head-major path."""
    cfg = cfglib.get_config("llama3-0.3b")
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    assert _plan((B, S, H, hd), (B, S, KV, hd)) == ("head_major", 1)
    calls = _kernel_calls(_model_layout_grad_hlo(one_chip, H, KV, hd))
    assert f"f32[{B},{H},{S},{hd}]" in calls["flash_attention_dq"]
    assert f"f32[{B},{H},1,{S}]" in calls["flash_attention_dq"]


# ---------------------------------------------------------------------------
# Newton–Schulz: every muon matrix of gpt2-12l
# ---------------------------------------------------------------------------

def _muon_shapes():
    """Distinct (n_in, n_out) of the leaves Muon orthogonalizes."""
    api = registry.get_model(GPT2)
    p = jax.eval_shape(lambda k: api.init(k, GPT2), jax.random.PRNGKey(0))
    out = set()
    for path, leaf in jax.tree_util.tree_flatten_with_path(p)[0]:
        if muon._is_matrix(path, leaf):
            out.add(tuple(leaf.shape[-2:]))
    return sorted(out)


def test_muon_shapes_cover_the_model():
    d, ff, V = GPT2.d_model, GPT2.d_ff, GPT2.vocab_size
    assert set(_muon_shapes()) == {(d, d), (d, ff), (ff, d), (V, d)}


@pytest.mark.parametrize("shape", [(768, 768), (768, 3072), (3072, 768),
                                   (50304, 768), (3072, 3072), (50304, 3072)])
def test_newton_schulz_compiles(one_chip, shape):
    x = _struct(shape, jnp.float32, one_chip)
    hlo = _compile(lambda m: ns_ops.newton_schulz_pallas(m, interpret=False),
                   x)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("shape", [(768, 768), (768, 3072),
                                   (15, 3072, 12288), (15, 12288, 3072)])
def test_newton_schulz_compiles_over_a_layer_stack(one_chip, shape):
    """Muon vmaps the kernel over the scanned layer stack: 12 layers of
    ``gpt2-12l``, or (three sizes given) 15 layers of ``gpt2-60l``'s MLP."""
    x = _struct(shape if len(shape) == 3 else (12,) + shape, jnp.float32,
                one_chip)
    _compile(jax.vmap(lambda m: ns_ops.newton_schulz_pallas(
        m, interpret=False)), x)


@pytest.fixture(scope="module")
def two_by_two(one_chip):
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))


def test_muon_orthogonalizes_a_sharded_stack_one_matrix_at_a_time(
        two_by_two, monkeypatch):
    """On the 2x2 mesh Muon gathers and orthogonalizes one matrix of a
    15-layer stack of ``gpt2-60l``'s 3072 x 12288 MLP at a time: the
    kernel runs in a loop, and the program's temporaries stay far under
    one gathered stack (2.26 GB)."""
    from repro.models import common
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    prev = common.get_active_mesh()
    common.set_active_mesh(two_by_two)
    try:
        x = _struct((15, 3072, 12288), jnp.float32,
                    NamedSharding(two_by_two, P(None, "data", "model")))
        compiled = jax.jit(lambda m: muon.orthogonalize(m)).lower(x).compile()
    finally:
        common.set_active_mesh(prev)
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and " while(" in hlo
    stack = 15 * 3072 * 12288 * 4
    assert compiled.memory_analysis().temp_size_in_bytes < stack / 2


@pytest.mark.parametrize("M,K,N,transpose_rhs", [
    (3072, 12288, 3072, True), (3072, 3072, 12288, False),
    (3072, 3072, 3072, False), (3072, 50304, 3072, True),
    (3072, 3072, 50304, False), (768, 50304, 768, True),
    (768, 768, 50304, False)])
def test_newton_schulz_matmul_compiles_at_its_planned_tiles(
        one_chip, M, K, N, transpose_rhs):
    """Every tiled product of ``gpt2-60l``'s and the embeddings' Newton–
    Schulz compiles at the tiles ``_plan`` gives, under the VMEM limit the
    kernel states, partial last blocks (N = 50304) included."""
    x = _struct((M, K), jnp.float32, one_chip)
    y = _struct((N, K) if transpose_rhs else (K, N), jnp.float32, one_chip)
    hlo = _compile(lambda a, b: ns_kernel.matmul(
        a, b, transpose_rhs=transpose_rhs, interpret=False), x, y)
    assert "tpu_custom_call" in hlo


def test_newton_schulz_fused_path_compiles_at_its_vmem_limit(one_chip):
    """The largest matrix the fused path accepts compiles under the VMEM
    limit the kernel states in its compiler params."""
    n = 128
    while ns_ops.fits_fused(n + 128, n + 128):
        n += 128
    x = _struct((n, n), jnp.float32, one_chip)
    _compile(lambda m: ns_kernel.ns_fused(m, interpret=False), x)


# ---------------------------------------------------------------------------
# paged decode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pool_dtype", [jnp.float32, jnp.int8])
@pytest.mark.parametrize("block_size", [16, 128])
def test_paged_decode_compiles(one_chip, pool_dtype, block_size):
    H, KV, hd = GPT2.num_heads, GPT2.num_kv_heads, GPT2.head_dim
    rows, max_len = 4, 1024
    nb = max_len // block_size
    NP = rows * nb + 1
    q = _struct((rows, 1, H, hd), jnp.float32, one_chip)
    pages = _struct((NP, block_size, KV, hd), pool_dtype, one_chip)
    table = _struct((rows, nb), jnp.int32, one_chip)
    index = _struct((rows,), jnp.int32, one_chip)
    args = [q, pages, pages, table, index]
    if pool_dtype == jnp.int8:
        scales = _struct((NP, block_size, KV, 1), jnp.float32, one_chip)

        def fn(q, kp, vp, t, i, ks, vs):
            return paged_attention_tpu(q, kp, vp, t, i, k_scales=ks,
                                       v_scales=vs, interpret=False)
        args += [scales, scales]
    else:
        def fn(q, kp, vp, t, i):
            return paged_attention_tpu(q, kp, vp, t, i, interpret=False)
    hlo = _compile(fn, *args)
    assert "tpu_custom_call" in hlo
