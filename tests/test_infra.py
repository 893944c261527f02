"""Checkpointing, data pipeline, optimizers, gradient compression,
sharding rules, HLO cost walker."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import checkpointer as ckpt
from repro.configs.base import ModelConfig, OptimizerConfig
from repro.data.synthetic import DataConfig, SyntheticLM, make_eval_batches
from repro.distributed import collectives as coll
from repro.distributed import sharding as shd
from repro.launch import mesh as mesh_lib
from repro.optim.base import global_norm, make_optimizer


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.float32),
            "b": {"c": jnp.ones((4,), jnp.bfloat16)}}
    ckpt.save(str(tmp_path), 10, tree, metadata={"num_layers": 2})
    assert ckpt.latest_step(str(tmp_path)) == 10
    like = jax.tree.map(jnp.zeros_like, tree)
    back = ckpt.restore(str(tmp_path), 10, like)
    np.testing.assert_array_equal(np.asarray(back["a"]), np.asarray(tree["a"]))
    assert ckpt.load_metadata(str(tmp_path), 10)["num_layers"] == 2


def test_checkpoint_keep_n_and_atomicity(tmp_path):
    tree = {"x": jnp.ones((2,))}
    for s in (1, 2, 3, 4):
        ckpt.save(str(tmp_path), s, tree, keep=2)
    assert ckpt.all_steps(str(tmp_path)) == [3, 4]
    # a stale .tmp dir must not be listed as a checkpoint
    os.makedirs(tmp_path / "step_000000099.tmp")
    assert ckpt.latest_step(str(tmp_path)) == 4


def test_async_checkpointer(tmp_path):
    ac = ckpt.AsyncCheckpointer()
    tree = {"x": jnp.arange(10)}
    ac.save(str(tmp_path), 5, tree)
    ac.wait()
    assert ckpt.latest_step(str(tmp_path)) == 5


def test_elastic_restore_reshards(tmp_path):
    """Restore with explicit shardings (re-shard on a different topology)."""
    tree = {"w": jnp.arange(16.0).reshape(4, 4)}
    ckpt.save(str(tmp_path), 1, tree)
    mesh = mesh_lib.make_mesh((1, 1), ("data", "model"))
    sh = {"w": jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(None, None))}
    back = ckpt.restore(str(tmp_path), 1, jax.tree.map(jnp.zeros_like, tree),
                        shardings=sh)
    np.testing.assert_array_equal(np.asarray(back["w"]),
                                  np.asarray(tree["w"]))


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_data_determinism_and_restart():
    cfg = DataConfig(vocab_size=128, seq_len=32, global_batch=8, seed=3)
    ds1, ds2 = SyntheticLM(cfg), SyntheticLM(cfg)
    b1 = ds1.batch(17)
    b2 = ds2.batch(17)                      # fresh object, same step
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert b1["tokens"].shape == (8, 32)
    # labels are next-token shifted
    full1 = ds1.batch(0)
    np.testing.assert_array_equal(full1["tokens"][:, 1:],
                                  full1["labels"][:, :-1])


def test_data_host_sharding():
    cfg = DataConfig(vocab_size=128, seq_len=16, global_batch=8, seed=0)
    ds = SyntheticLM(cfg)
    shards = [ds.batch(5, shard=i, num_shards=4) for i in range(4)]
    assert all(s["tokens"].shape == (2, 16) for s in shards)
    # distinct shards produce distinct data
    assert not np.array_equal(shards[0]["tokens"], shards[1]["tokens"])


def test_eval_batches_disjoint_from_train():
    cfg = DataConfig(vocab_size=128, seq_len=16, global_batch=4, seed=0)
    ds = SyntheticLM(cfg)
    evals = make_eval_batches(cfg, 2)
    assert not np.array_equal(evals[0]["tokens"], ds.batch(0)["tokens"])


# ---------------------------------------------------------------------------
# optimizers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["muon_nsgd", "adamw", "nsgd", "sgd"])
def test_optimizers_reduce_quadratic(name):
    opt = make_optimizer(OptimizerConfig(name=name, learning_rate=0.05,
                                         weight_decay=0.0))
    params = {"w": jnp.ones((8, 16)) * 2.0, "b": jnp.ones((16,))}
    state = opt.init(params)

    def loss(p):
        return jnp.sum(p["w"] ** 2) + jnp.sum(p["b"] ** 2)

    l0 = float(loss(params))
    for _ in range(100):
        grads = jax.grad(loss)(params)
        params, state = opt.update(grads, state, params, 0.05)
    # Muon's orthogonalized step moves at a fixed spectral rate — slower on
    # this rank-1 toy than elementwise optimizers, hence the loose bound.
    assert float(loss(params)) < l0 * 0.75, name


def test_muon_update_is_orthogonalized():
    """After one Muon step from zero momentum, the weight delta must be a
    near-orthogonal matrix times lr*scale."""
    opt = make_optimizer(OptimizerConfig(name="muon_nsgd", learning_rate=0.1,
                                         weight_decay=0.0, momentum=0.0,
                                         mup=False))
    w0 = jnp.zeros((32, 64))
    params = {"w": w0}
    g = {"w": jax.random.normal(jax.random.PRNGKey(0), (32, 64))}
    new, _ = opt.update(g, opt.init(params), params, 0.1)
    delta = (new["w"] - w0) / -0.1
    s = jnp.linalg.svd(delta, compute_uv=False)
    assert float(s.max()) < 1.4 and float(s.min()) > 0.3
    # regression (name-collision bug): a top-level matrix named "w" must get
    # Muon, not the NSGD path reserved for token-shift mu subkeys
    assert float(jnp.median(s)) > 0.5


def test_muon_stacked_leaves_per_layer():
    """Stacked block matrices are orthogonalized per layer (vmap)."""
    from repro.optim.muon import orthogonalize
    m = jax.random.normal(jax.random.PRNGKey(0), (3, 32, 32))
    y = orthogonalize(m)
    for i in range(3):
        s = jnp.linalg.svd(y[i], compute_uv=False)
        assert float(s.max()) < 1.4


def test_muon_orthogonalize_on_one_device_is_the_vmap_over_the_stack():
    """Without a mesh, or on a one-device mesh, ``orthogonalize`` is the
    same function it always was: Newton-Schulz vmapped over the stack."""
    from repro.kernels.newton_schulz import ops as ns_ops
    from repro.models import common
    from repro.optim.muon import orthogonalize

    def vmapped(m, steps=5):
        x = m.reshape((-1,) + m.shape[-2:])
        y = jax.vmap(lambda a: ns_ops.newton_schulz(a, steps=steps))(x)
        return y.reshape(m.shape)

    prev = common.get_active_mesh()
    try:
        for mesh in (None, mesh_lib.single_device_mesh()):
            common.set_active_mesh(mesh)
            for shape in ((3, 16, 32), (16, 32)):
                m = jnp.ones(shape)
                # a fresh function each time: jit caches traces by function
                assert str(jax.make_jaxpr(lambda x: orthogonalize(x))(m)) \
                    == str(jax.make_jaxpr(vmapped)(m))
    finally:
        common.set_active_mesh(prev)


def test_muon_orthogonalize_layer_by_layer_on_a_mesh_equals_one_device():
    """On a 2x2 mesh a stack is orthogonalized one matrix at a time
    (``lax.map``) and gives what the one-device path gives."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.models import common
    from repro.optim.muon import orthogonalize
    m = jax.random.normal(jax.random.PRNGKey(1), (3, 32, 64))
    want = jax.jit(lambda x: orthogonalize(x))(m)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
    prev = common.get_active_mesh()
    common.set_active_mesh(mesh)
    try:
        assert "scan" in str(jax.make_jaxpr(lambda x: orthogonalize(x))(m))
        got = jax.jit(lambda x: orthogonalize(x))(jax.device_put(
            m, NamedSharding(mesh, P(None, "data", "model"))))
    finally:
        common.set_active_mesh(prev)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-6)


def test_grad_clip():
    from repro.optim.base import clip_by_global_norm
    g = {"a": jnp.ones((10,)) * 100.0}
    c = clip_by_global_norm(g, 1.0)
    assert abs(float(global_norm(c)) - 1.0) < 1e-4


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------

def test_int8_compression_error_feedback():
    g = {"w": jax.random.normal(jax.random.PRNGKey(0), (64, 64))}
    ef = coll.init_error_feedback(g)
    comp, ef = coll.compress_grads_with_ef(g, ef)
    back = coll.decompress_grads(comp)
    rel = float(jnp.linalg.norm(back["w"] - g["w"]) / jnp.linalg.norm(g["w"]))
    assert rel < 0.02
    # error feedback accumulates the quantization residual
    assert float(jnp.abs(ef["w"]).max()) > 0
    # applying EF on a repeated constant gradient drives cumulative error down
    total = jnp.zeros_like(g["w"])
    ef = coll.init_error_feedback(g)
    for _ in range(8):
        comp, ef = coll.compress_grads_with_ef(g, ef)
        total = total + coll.decompress_grads(comp)["w"]
    rel_cum = float(jnp.linalg.norm(total / 8 - g["w"])
                    / jnp.linalg.norm(g["w"]))
    assert rel_cum < 0.005


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

def _mesh11():
    return mesh_lib.make_mesh((1, 1), ("data", "model"))


def test_param_specs_shapes():
    from jax.tree_util import DictKey
    mesh = _mesh11()

    class FakeLeaf:
        def __init__(self, shape):
            self.shape = shape
            self.ndim = len(shape)

    # moe expert stack inside blocks: experts on 'model' (dim 1 after scan axis)
    spec = shd.param_spec((DictKey("blocks"), DictKey("layer0"),
                           DictKey("moe"), DictKey("w_gate")),
                          FakeLeaf((4, 64, 32, 128)), mesh, fsdp=False)
    assert spec[1] == "model" and spec[0] is None
    # dense ffn w_down: contraction dim
    spec = shd.param_spec((DictKey("blocks"), DictKey("layer0"),
                           DictKey("mlp"), DictKey("w_down")),
                          FakeLeaf((4, 128, 64)), mesh, fsdp=False)
    assert spec[1] == "model"
    # embed: vocab
    spec = shd.param_spec((DictKey("embed"),), FakeLeaf((1000, 64)), mesh,
                          fsdp=False)
    assert spec[0] == "model"
    # norm scale: replicated
    spec = shd.param_spec((DictKey("final_norm"), DictKey("scale")),
                          FakeLeaf((64,)), mesh, fsdp=False)
    assert all(s is None for s in spec)


def test_cache_shardings_kv():
    mesh = _mesh11()
    cache = {"k": jax.ShapeDtypeStruct((4, 8, 1024, 2, 64), jnp.bfloat16)}
    sh = shd.cache_shardings(cache, mesh)
    spec = sh["k"].spec
    assert spec[0] is None                   # super-block axis never sharded


# ---------------------------------------------------------------------------
# HLO cost walker
# ---------------------------------------------------------------------------

def test_hlo_walker_counts_loop_trips():
    from repro.roofline import hlo_cost
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)

    def scanned(a, b):
        def body(c, _):
            return c @ b, None
        out, _ = jax.lax.scan(body, a, None, length=9)
        return out

    text = jax.jit(scanned).lower(x, x).compile().as_text()
    r = hlo_cost.analyze(text)
    expect = 9 * 2 * 64 ** 3
    assert expect * 0.9 < r["flops"] < expect * 1.5


def test_straggler_monitor():
    m = coll.StragglerMonitor(window=20, threshold=2.0)
    import time
    for _ in range(15):
        m.start()
        time.sleep(0.001)
        m.stop()
    m.start()
    time.sleep(0.05)
    _, slow = m.stop()
    assert slow


# ---------------------------------------------------------------------------
# persistent compilation cache placement
# ---------------------------------------------------------------------------

def test_compile_cache_placed_from_outside(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code; without
    it the cache goes to one fixed, gitignored directory of the checkout.
    Importing the module turned nothing on."""
    from pathlib import Path

    from repro.launch import compile_cache
    prev = jax.config.jax_compilation_cache_dir
    root = Path(__file__).resolve().parents[1]
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert compile_cache.enable() == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(root / ".jax_cache")
        assert ".jax_cache/" in (root / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
