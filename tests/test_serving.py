"""Mesh-sharded serving tests on 8 fake CPU devices (flags in conftest.py).

The ``ServeEngine`` must be a *numerical no-op* relative to single-device
generation: greedy tokens byte-identical on a data-parallel mesh, logits
within float tolerance under tensor parallelism, and a depth-expanded
(function-preserving) checkpoint must serve the exact token stream of its
source model — the paper's drop-in-continuation claim at decode time.
Structurally: prefill is ONE compiled forward (cache/logits equivalent to a
token-by-token decode of the prompt), and the decode loop moves nothing
device->host (donated sharded caches, fused sampling).
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.checkpoint import checkpointer as ckpt
from repro.configs.base import ModelConfig, MoEConfig, SSMConfig
from repro.core import expansion as exp
from repro.distributed import sharding as shd
from repro.launch import mesh as mesh_lib
from repro.models import registry
from repro.train import steps as steps_lib
from repro.train.serve_engine import ServeEngine

CFG_DENSE = ModelConfig(name="srv-dense", family="dense", num_layers=4,
                        d_model=32, num_heads=4, num_kv_heads=2, d_ff=64,
                        vocab_size=64, max_seq_len=64)
CFG_WINDOW = dataclasses.replace(CFG_DENSE, name="srv-window",
                                 window_pattern=(4, 0))
CFG_MAMBA = ModelConfig(name="srv-mamba", family="ssm", num_layers=4,
                        d_model=32, num_heads=4, num_kv_heads=4, d_ff=64,
                        vocab_size=64, max_seq_len=64, attention="none",
                        position="none", block_pattern=("mamba",),
                        ssm=SSMConfig(d_state=4))
CFG_RWKV = ModelConfig(name="srv-rwkv", family="ssm", num_layers=4,
                       d_model=32, num_heads=4, num_kv_heads=4, d_ff=64,
                       vocab_size=64, max_seq_len=64, attention="none",
                       position="none", norm="layernorm",
                       block_pattern=("rwkv",),
                       ssm=SSMConfig(kind="rwkv6", head_dim=16))
CFG_MLA = dataclasses.replace(CFG_DENSE, name="srv-mla", attention="mla",
                              mla_kv_lora_rank=8)
# capacity_factor = num_experts / top_k: no token is dropped, so rows stay
# independent and a row's logits do not depend on its batch.
CFG_MOE = dataclasses.replace(CFG_DENSE, name="srv-moe", family="moe",
                              moe=MoEConfig(num_experts=4, top_k=2,
                                            capacity_factor=2.0))
CFG_HYBRID = dataclasses.replace(CFG_DENSE, name="srv-hybrid",
                                 family="hybrid",
                                 block_pattern=("attn", "mamba"),
                                 ssm=SSMConfig(d_state=4))
ARCH_CFGS = {"dense": CFG_DENSE, "window": CFG_WINDOW, "mamba": CFG_MAMBA,
             "rwkv": CFG_RWKV, "mla": CFG_MLA, "moe": CFG_MOE,
             "hybrid": CFG_HYBRID}


def _params(cfg, seed=0):
    return registry.get_model(cfg).init(jax.random.PRNGKey(seed), cfg)


def _prompts(cfg, B=8, P=8, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (B, P)).astype(np.int32)


# ---------------------------------------------------------------------------
# Sharded vs single-device parity
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["dense", "mamba", "rwkv"])
def test_sharded_greedy_matches_single_device(arch):
    """8-device data-parallel greedy decode == single device, byte for byte
    (per-example math is untouched by batch sharding); logits within 1e-4."""
    cfg = ARCH_CFGS[arch]
    params = _params(cfg)
    prompts = _prompts(cfg)
    single = ServeEngine(cfg, params, mesh=mesh_lib.single_device_mesh(),
                         max_len=32)
    sharded = ServeEngine(cfg, params, mesh=mesh_lib.make_train_mesh("host"),
                          max_len=32)
    r1 = single.generate(prompts, 12, return_logits=True)
    r2 = sharded.generate(prompts, 12, return_logits=True)
    np.testing.assert_array_equal(r1.tokens, r2.tokens)
    np.testing.assert_allclose(r2.logits, r1.logits, rtol=0, atol=1e-4)
    assert r1.steps == r2.steps == 12
    assert r1.prefill_tokens == prompts.shape[1]


@pytest.mark.slow
def test_tensor_parallel_greedy_matches_single_device():
    """(4 data, 2 model) mesh: TP reassociates reductions, so logits carry
    float noise (<=1e-4) but greedy tokens still match exactly."""
    params = _params(CFG_DENSE)
    prompts = _prompts(CFG_DENSE)
    single = ServeEngine(CFG_DENSE, params,
                         mesh=mesh_lib.single_device_mesh(), max_len=32)
    tp = ServeEngine(CFG_DENSE, params, mesh=mesh_lib.make_train_mesh("4x2"),
                     max_len=32)
    r1 = single.generate(prompts, 12, return_logits=True)
    r2 = tp.generate(prompts, 12, return_logits=True)
    np.testing.assert_array_equal(r1.tokens, r2.tokens)
    np.testing.assert_allclose(r2.logits, r1.logits, rtol=0, atol=1e-4)


# ---------------------------------------------------------------------------
# Function preservation at decode time (through a depth expansion)
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["dense", "mamba"])
def test_expanded_checkpoint_serves_identically(arch):
    """Serving a depth-expanded (copying_zeroL) checkpoint on the 8-device
    mesh produces the *identical* token stream as the pre-expansion params on
    one device: the new blocks are exact identities (zeroed last linears), so
    the expanded model is a drop-in continuation at decode time (§3.1)."""
    cfg2 = ARCH_CFGS[arch].with_depth(2)
    cfg4 = ARCH_CFGS[arch].with_depth(4)
    params2 = _params(cfg2)
    params4 = exp.expand_params(params2, cfg2, 4, "copying_zeroL")
    prompts = _prompts(cfg2)
    before = ServeEngine(cfg2, params2, mesh=mesh_lib.single_device_mesh(),
                         max_len=32).generate(prompts, 12)
    after = ServeEngine(cfg4, params4, mesh=mesh_lib.make_train_mesh("host"),
                        max_len=32).generate(prompts, 12)
    np.testing.assert_array_equal(before.tokens, after.tokens)


# ---------------------------------------------------------------------------
# True prefill: one forward == token-by-token decode of the prompt
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", list(ARCH_CFGS))
def test_prefill_matches_token_by_token_decode(arch):
    """The compiled full-sequence prefill leaves the same cache and last-token
    logits a token-by-token decode of the prompt would (incl. the windowed
    ring buffer), so prefill->decode and decode-only histories agree."""
    cfg = ARCH_CFGS[arch]
    api = registry.get_model(cfg)
    params = _params(cfg)
    B, P, ML = 2, 8, 16
    toks = jnp.asarray(_prompts(cfg, B=B, P=P))
    cache0 = api.init_cache(params, cfg, B, ML, dtype=jnp.float32)
    logits_pf, cache_pf = jax.jit(
        functools.partial(api.prefill, cfg=cfg))(params, tokens=toks,
                                                 cache=cache0)
    cache = api.init_cache(params, cfg, B, ML, dtype=jnp.float32)
    decode = steps_lib.make_decode_step(cfg)
    logits_dec = None
    for t in range(P):
        logits_dec, cache = decode(params, toks[:, t:t + 1], cache,
                                   jnp.int32(t))
    np.testing.assert_allclose(np.asarray(logits_pf[:, -1]),
                               np.asarray(logits_dec[:, 0]),
                               rtol=0, atol=1e-4)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=0, atol=1e-4), cache_pf, cache)
    # and the prefill forward is the train-path forward
    full = api.apply(params, cfg, {"tokens": toks})
    np.testing.assert_allclose(np.asarray(logits_pf), np.asarray(full),
                               rtol=0, atol=1e-4)


@pytest.mark.parametrize("arch", list(ARCH_CFGS))
def test_chunked_prefill_and_verify_match_apply(arch):
    """The two other multi-token entry points score what the train-path
    forward scores over the whole sequence: a prompt prefilled in two
    chunks through a paged cache (last-position logits of each chunk), then
    a 3-token verify at the prompt's end (logits at all 3 positions)."""
    cfg = ARCH_CFGS[arch]
    api = registry.get_model(cfg)
    params = _params(cfg)
    C, G, BS, ML = 4, 3, 4, 16
    toks = jnp.asarray(_prompts(cfg, B=1, P=2 * C + G))
    full = np.asarray(api.apply(params, cfg, {"tokens": toks}))
    table = jnp.arange(ML // BS, dtype=jnp.int32)[None]
    cache = api.init_paged_cache(params, cfg, 1, ML // BS, BS, ML,
                                 dtype=jnp.float32)
    carry = api.init_prefill_carry(params, cfg, ML, dtype=jnp.float32)
    chunk = jax.jit(functools.partial(api.prefill_chunk, cfg=cfg))
    for c in range(2):
        logits, cache, carry = chunk(params, tokens=toks[:, c * C:(c + 1) * C],
                                     cache=cache, carry=carry,
                                     block_table=table, ctx_len=c * C)
        np.testing.assert_allclose(np.asarray(logits[:, 0]),
                                   full[:, (c + 1) * C - 1],
                                   rtol=0, atol=1e-4)
    # Admit the row: carried layers (window rings, recurrent states) take
    # their carry; paged layers (which carry {}) keep the pool.
    cache = {n: c if c else cache[n] for n, c in carry.items()}
    logits, _ = jax.jit(functools.partial(api.verify, cfg=cfg))(
        params, tokens=toks[:, 2 * C:], cache=cache,
        index=jnp.full((1,), 2 * C, jnp.int32), block_table=table,
        write_mask=jnp.ones((1, G), bool))
    np.testing.assert_allclose(np.asarray(logits), full[:, 2 * C:],
                               rtol=0, atol=1e-4)


# ---------------------------------------------------------------------------
# Donated sharded caches: no host transfer in the decode loop
# ---------------------------------------------------------------------------


def test_decode_loop_no_host_transfer():
    """Same check as test_distributed's expansion transfer guard: once
    prompts are placed, generation up to the final token fetch moves nothing
    device->host — sampling is fused into the decode step and the cache is
    donated on device."""
    params = _params(CFG_DENSE)
    prompts = _prompts(CFG_DENSE)
    eng = ServeEngine(CFG_DENSE, params,
                      mesh=mesh_lib.make_train_mesh("host"), max_len=32)
    eng.generate(prompts, 4)                        # compile outside the guard
    with jax.transfer_guard_device_to_host("disallow"):
        tokens, _, _ = eng.generate_arrays(prompts, 8)
        jax.block_until_ready(tokens)
    assert np.asarray(tokens).shape == (8, 16)


def test_engine_cache_shardings_and_donation():
    """Engine caches live in the layout cache_shardings assigns, keep it
    across prefill and decode (out_shardings), and the decode step consumes
    its donated input cache."""
    mesh = mesh_lib.make_train_mesh("4x2")
    params = _params(CFG_DENSE)
    eng = ServeEngine(CFG_DENSE, params, mesh=mesh, max_len=16)
    B = 8
    prefill, decode, sh, init_cache = eng._steps(B, 0.0)
    cache = init_cache(eng.params)
    jax.tree.map(lambda x, s: None if x.sharding == s else
                 pytest.fail(f"{x.sharding} != {s}"), cache, sh.cache)
    toks = jax.device_put(_prompts(CFG_DENSE, B=B, P=4), sh.tokens)
    key = jax.device_put(jax.random.PRNGKey(0), sh.replicated)
    # greedy executables take no temperature operand (dead for argmax)
    nxt, _, cache, index, key = prefill(eng.params, toks, cache, key)
    jax.tree.map(lambda x, s: None if x.sharding == s else
                 pytest.fail(f"{x.sharding} != {s}"), cache, sh.cache)
    old_leaves = jax.tree.leaves(cache)
    nxt, _, cache, index, key = decode(eng.params, nxt, cache, index, key)
    jax.tree.map(lambda x, s: None if x.sharding == s else
                 pytest.fail(f"{x.sharding} != {s}"), cache, sh.cache)
    # donated: the previous cache buffers were consumed by the step
    assert all(x.is_deleted() for x in old_leaves)


def test_temperature_shares_one_compiled_step():
    """Temperature is a traced operand: distinct values reuse one executable
    (per batch size and greedy/sample mode), deterministically per seed."""
    params = _params(CFG_DENSE)
    eng = ServeEngine(CFG_DENSE, params, max_len=32)
    prompts = _prompts(CFG_DENSE, B=2, P=4)
    r1 = eng.generate(prompts, 4, temperature=0.7, seed=3)
    r2 = eng.generate(prompts, 4, temperature=1.3, seed=3)
    r3 = eng.generate(prompts, 4, temperature=0.7, seed=3)
    assert len(eng._built) == 1          # one (batch, sample-mode) entry
    np.testing.assert_array_equal(r1.tokens, r3.tokens)
    assert r1.tokens.shape == r2.tokens.shape


def test_generate_steps_accounting():
    """Prefill is one fused call, not P decode steps: `steps` counts
    generated tokens only and the prompt length is reported separately."""
    params = _params(CFG_DENSE)
    eng = ServeEngine(CFG_DENSE, params, max_len=32)
    res = eng.generate(_prompts(CFG_DENSE, B=2, P=5), 7)
    assert res.steps == 7
    assert res.prefill_tokens == 5
    assert res.tokens.shape == (2, 12)


# ---------------------------------------------------------------------------
# Checkpoint -> serve: params-only subtree restore, sharded onto the mesh
# ---------------------------------------------------------------------------


def test_checkpoint_subtree_restore_for_serving(tmp_path):
    """A serving process restores the params subtree by manifest keypaths —
    no optimizer-state structure needed — re-sharded onto its own mesh, and
    the restored model generates the saved model's exact tokens."""
    params = _params(CFG_DENSE)
    tree = {"params": params,
            "opt_state": {"m": jax.tree.map(jnp.zeros_like, params),
                          "step": jnp.zeros((), jnp.int32)}}
    ckpt.save(str(tmp_path), 3, tree, metadata={"num_layers": 4})

    mesh = mesh_lib.make_train_mesh("4x2")
    p_struct = jax.eval_shape(lambda t: t, params)
    p_sh = shd.params_shardings(p_struct, mesh, fsdp=False)
    back = ckpt.restore_subtree(str(tmp_path), 3, p_struct, "params",
                                shardings=p_sh)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), back, params)
    assert all(x.sharding.mesh == mesh for x in jax.tree.leaves(back))
    prompts = _prompts(CFG_DENSE)
    r_src = ServeEngine(CFG_DENSE, params,
                        mesh=mesh_lib.single_device_mesh(),
                        max_len=24).generate(prompts, 6)
    r_ckpt = ServeEngine(CFG_DENSE, back, mesh=mesh,
                         max_len=24).generate(prompts, 6)
    np.testing.assert_array_equal(r_src.tokens, r_ckpt.tokens)
    with pytest.raises(KeyError):
        ckpt.restore_subtree(str(tmp_path), 3,
                             {"nope": jax.ShapeDtypeStruct((1,), jnp.float32)},
                             "params")


# ---------------------------------------------------------------------------
# distributed.sharding.cache_shardings unit tests
# ---------------------------------------------------------------------------


def _spec(shardings, name):
    """The leaf's PartitionSpec; compared against P(...) literals, which
    normalizes a one-axis tuple entry ``("data",)`` to ``"data"``."""
    return shardings[name].spec


def test_cache_shardings_batch_and_model_dims():
    mesh = mesh_lib.make_train_mesh("4x2")
    specs = {"k": jax.ShapeDtypeStruct((3, 8, 24, 2, 16), jnp.float32)}
    sh = shd.cache_shardings(specs, mesh)
    # batch (dim 1) over 'data', longest remaining dim (seq=24) over 'model'
    assert _spec(sh, "k") == P(None, "data", "model", None, None)


def test_cache_shardings_batch_over_pod_and_data():
    mesh = mesh_lib.make_mesh((2, 2, 2), ("pod", "data", "model"))
    specs = {"s": jax.ShapeDtypeStruct((2, 8, 4, 16), jnp.float32)}
    sh = shd.cache_shardings(specs, mesh)
    spec = _spec(sh, "s")
    assert tuple(spec[1]) == ("pod", "data")


def test_cache_shardings_indivisible_falls_back_to_replication():
    mesh = mesh_lib.make_train_mesh("4x2")
    specs = {"odd": jax.ShapeDtypeStruct((3, 6, 5, 3), jnp.float32)}
    sh = shd.cache_shardings(specs, mesh)
    # 6 % 4 != 0 (batch), 5/3 % 2 != 0 (model): fully replicated, compiles
    assert _spec(sh, "odd") == P(None, None, None, None)


def test_cache_shardings_never_shards_superblock_axis():
    mesh = mesh_lib.make_train_mesh("4x2")
    # dim 0 (n_super) is both divisible and the longest dim — still unsharded
    specs = {"v": jax.ShapeDtypeStruct((64, 8, 4, 2), jnp.float32)}
    sh = shd.cache_shardings(specs, mesh)
    spec = _spec(sh, "v")
    assert spec[0] is None
    assert spec == P(None, "data", "model", None)
