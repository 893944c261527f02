"""Property-based tests (hypothesis) on system invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import expansion as exp
from repro.core.schedules import cosine, wsd
from repro.core.mixing import compute_savings
from repro.data.synthetic import DataConfig, SyntheticLM
from repro.models.common import cross_entropy, softcap
from repro.roofline.analysis import collective_bytes

SET = settings(max_examples=25, deadline=None)


# ---------------------------------------------------------------------------
# expansion index maps
# ---------------------------------------------------------------------------

@SET
@given(n_src=st.integers(1, 8), extra=st.integers(0, 16),
       method=st.sampled_from(["copying_stack", "copying_inter",
                               "copying_last"]))
def test_index_map_invariants(n_src, extra, method):
    n_tgt = n_src + extra
    idx = exp._source_index_map(n_src, n_tgt, method)
    assert len(idx) == n_tgt
    assert all(0 <= i < n_src for i in idx)
    assert set(idx) == set(range(n_src))          # every source used
    if method == "copying_inter":
        assert idx == sorted(idx)                 # interpolation is ordered
    if method == "copying_last":
        assert idx[:n_src] == list(range(n_src))  # prefix preserved


@SET
@given(n_src=st.integers(0, 4), extra=st.integers(1, 6),
       insert_at=st.sampled_from(["bottom", "top"]),
       method=st.sampled_from(["random", "zero"]))
def test_expand_stack_preserves_source(n_src, extra, insert_at, method):
    n_tgt = n_src + extra
    old = {"w": jnp.arange(n_src * 4, dtype=jnp.float32).reshape(n_src, 2, 2)} \
        if n_src else None
    fresh = {"w": jnp.full((n_tgt, 2, 2), 99.0)}
    out = exp.expand_stack(old, n_tgt, method, fresh_stack=fresh,
                           insert_at=insert_at)
    assert out["w"].shape == (n_tgt, 2, 2)
    if n_src:
        sl = slice(0, n_src) if insert_at == "bottom" else slice(-n_src, None)
        np.testing.assert_array_equal(np.asarray(out["w"][sl]),
                                      np.asarray(old["w"]))
        new_sl = slice(n_src, None) if insert_at == "bottom" else slice(0, extra)
        if method == "zero":
            assert float(jnp.abs(out["w"][new_sl]).sum()) == 0.0


# ---------------------------------------------------------------------------
# schedules
# ---------------------------------------------------------------------------

@SET
@given(total=st.integers(50, 5000), peak=st.floats(1e-4, 1.0),
       warm=st.floats(0.01, 0.1), decay=st.floats(0.05, 0.5))
def test_wsd_bounds_and_plateau(total, peak, warm, decay):
    fn = wsd(peak, total, warmup_frac=warm, decay_frac=decay)
    t = np.arange(total)
    lrs = np.asarray(jax.vmap(fn)(jnp.asarray(t)))
    assert (lrs <= peak + 1e-9).all() and (lrs >= -1e-12).all()
    stable_end = total - max(1, int(total * decay))
    warm_end = max(1, int(total * warm))
    if warm_end + 2 < stable_end:
        mid = lrs[warm_end + 1:stable_end]
        assert np.allclose(mid, peak, rtol=1e-5)


@SET
@given(total=st.integers(50, 2000), peak=st.floats(1e-4, 1.0))
def test_cosine_monotone_after_warmup(total, peak):
    fn = cosine(peak, total)
    t = np.arange(total)
    lrs = np.asarray(jax.vmap(fn)(jnp.asarray(t)))
    warm_end = max(1, int(total * 0.02))
    assert (np.diff(lrs[warm_end + 1:]) <= 1e-7).all()


# ---------------------------------------------------------------------------
# savings formula (eq 1.1)
# ---------------------------------------------------------------------------

@SET
@given(T=st.integers(100, 10**6), frac=st.floats(0.05, 0.95),
       n_small=st.floats(1e6, 1e9), ratio=st.floats(1.1, 100.0))
def test_savings_bounds(T, frac, n_small, ratio):
    tau = int(T * frac)
    n_large = n_small * ratio
    out = compute_savings(T, tau, n_small, n_large, 1000)
    assert 0.0 <= out["savings"] < 1.0
    assert out["speedup"] >= 1.0
    # exact identity
    assert abs(out["savings"] - (1 - 1 / out["speedup"])) < 1e-9


# ---------------------------------------------------------------------------
# numerics
# ---------------------------------------------------------------------------

@SET
@given(cap=st.floats(1.0, 100.0), scale=st.floats(0.1, 1000.0))
def test_softcap_bounded_and_monotone(cap, scale):
    x = jnp.linspace(-scale, scale, 101)
    y = softcap(x, cap)
    assert float(jnp.abs(y).max()) <= cap + 1e-5
    # Near saturation tanh moves in float32 ulps of 1.0, which the cap
    # multiplies: adjacent outputs may dip by at most cap x one ulp.
    ulp = float(np.finfo(np.float32).eps)
    assert bool(jnp.all(jnp.diff(y) >= -cap * ulp))


@SET
@given(b=st.integers(1, 4), s=st.integers(1, 8), v=st.integers(2, 50))
def test_cross_entropy_matches_manual(b, s, v):
    key = jax.random.PRNGKey(b * 100 + s)
    logits = jax.random.normal(key, (b, s, v))
    labels = jax.random.randint(key, (b, s), 0, v)
    ce = float(cross_entropy(logits, labels))
    probs = jax.nn.log_softmax(logits, -1)
    manual = -float(jnp.take_along_axis(probs, labels[..., None], -1).mean())
    assert abs(ce - manual) < 1e-4
    assert ce <= np.log(v) * 3 + 5


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

@SET
@given(seed=st.integers(0, 1000), step=st.integers(0, 10**6))
def test_synthetic_data_deterministic(seed, step):
    cfg = DataConfig(vocab_size=64, seq_len=8, global_batch=2, seed=seed)
    a = SyntheticLM(cfg).batch(step)
    b = SyntheticLM(cfg).batch(step)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert a["tokens"].min() >= 0 and a["tokens"].max() < 64


# ---------------------------------------------------------------------------
# HLO collective parser
# ---------------------------------------------------------------------------

def test_collective_parser_on_synthetic_hlo():
    hlo = """
  %all-gather.5 = bf16[16,4096,7168]{2,1,0} all-gather(%p), replica_groups=...
  %ar = f32[256,1024]{1,0} all-reduce(%x), to_apply=%add
  %rs.2 = f32[64]{0} reduce-scatter(%y), dimensions={0}
  %nothing = f32[8]{0} add(%a, %b)
"""
    by = collective_bytes(hlo)
    assert by["all-gather"] == 16 * 4096 * 7168 * 2
    assert by["all-reduce"] == 256 * 1024 * 4
    assert by["reduce-scatter"] == 64 * 4
    assert "add" not in by
