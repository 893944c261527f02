"""Counts of operations and bytes, and the peak table."""
import jax
import jax.numpy as jnp
import pytest

import counts
from reference import gpt2 as ref

M = {"d_model": 8, "d_ff": 32, "num_heads": 2, "num_kv_heads": 2,
     "head_dim": 4, "vocab_size": 16, "num_layers": 1, "max_seq_len": 4}


def test_peak_table_has_v5e_and_refuses_unknown():
    p = counts.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16 * 2 ** 30
    with pytest.raises(counts.UnknownDevice):
        counts.peaks("TPU v9 imaginary")
    with pytest.raises(counts.UnknownDevice):
        counts.peaks("cpu")


def test_hand_worked_model_counts():
    # per layer: q,k,v,o 4 * 8*8 = 256, mlp 2 * 8*32 = 512
    assert counts.layer_matmul_params(M) == 768
    assert counts.causal_pairs(4) == 10
    # forward, batch 1, seq 4: 2*4*(768 + 8*16) + 4*2*4*10
    assert counts.forward_flops(M, 1, 1, 4) == 2 * 4 * 896 + 320
    assert counts.train_step_flops(M, 1, 1, 4) == 3 * (7168 + 320)


def test_hand_worked_kernel_counts():
    # NS on 2x3: m=2, n=3: per iteration 4*4*3 + 2*8 = 64, five of them
    assert counts.newton_schulz(3, 2) == (320.0, 2 * 6 * 4.0)
    f, b = counts.flash_attention_train(M, 1, 1, 4)
    assert f == 7 * 2 * 2 * 4 * 10
    assert b == 12 * 4 * 8 * 4
    assert counts.roofline_s(197e12, 0, {"flops_per_s": 197e12,
                                         "hbm_bytes_per_s": 1.0}) \
        == (1.0, "compute")


def _xla_flops(fn, *args):
    return jax.jit(fn).lower(*args).compile().cost_analysis()["flops"]


@pytest.mark.parametrize("shape", [(24, 40), (40, 24), (32, 32)])
def test_newton_schulz_count_is_not_above_a_plain_implementation(shape):
    x = jnp.ones(shape, jnp.float32)
    f, _ = counts.newton_schulz(*shape)
    assert f <= _xla_flops(ref.newton_schulz, x)


def test_attention_count_is_not_above_a_plain_implementation():
    B, S, H, hd = 2, 16, 2, 8
    m = dict(M, num_heads=H, num_kv_heads=H, head_dim=hd, d_model=H * hd)
    q = jnp.ones((B, S, H, hd), jnp.float32)

    def attn(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)

    def fwd_bwd(q, k, v):
        return jax.grad(lambda *a: jnp.sum(attn(*a)), argnums=(0, 1, 2))(
            q, k, v)

    fwd = 2 * 2 * H * hd * B * counts.causal_pairs(S)
    assert fwd <= _xla_flops(attn, q, q, q)
    f, _ = counts.flash_attention_train(m, 1, B, S)
    assert f <= _xla_flops(fwd_bwd, q, q, q) + _xla_flops(attn, q, q, q)


def test_model_count_is_not_above_a_plain_forward():
    m = dict(M, max_seq_len=8)
    p = ref.init(jax.random.PRNGKey(0), m, 2)
    t = jnp.zeros((2, 8), jnp.int32)
    assert counts.forward_flops(m, 2, 2, 8) <= _xla_flops(
        lambda p, t: ref.logits(p, m, t, 1e-6), p, t)
