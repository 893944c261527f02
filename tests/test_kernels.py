"""Pallas kernel validation: interpret-mode kernel vs pure-jnp oracle,
swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as cfglib
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.flash_attention.kernel import _plan, flash_attention_tpu
from repro.kernels.mamba_scan.kernel import selective_scan_tpu
from repro.kernels.mamba_scan.ref import selective_scan_ref
from repro.kernels.newton_schulz import kernel as ns_kernel
from repro.kernels.newton_schulz import ops as ns_ops
from repro.kernels.newton_schulz.ref import newton_schulz_ref
from repro.kernels.paged_attention import ref as pa_ref
from repro.kernels.paged_attention.kernel import paged_attention_tpu
from repro.kernels.rwkv6.kernel import wkv_tpu
from repro.kernels.rwkv6.ref import wkv_ref
from repro.launch import mesh as mesh_lib
from repro.models import common, transformer


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

# (H, KV, hd) -> the layout ``_plan`` must choose for it.
FLASH_PLANS = {
    (2, 2, 32): ("head_major", 1),    # 2 heads of 32: half a 128-lane block
    (4, 2, 64): ("head_major", 1),    # GQA at hd 64
    (4, 1, 16): ("head_major", 1),
    (4, 2, 16): ("head_major", 1),
    (2, 2, 16): ("head_major", 1),
    (2, 1, 16): ("head_major", 1),
    (4, 4, 64): ("lane_dense", 2),    # MHA at hd 64, as gpt2
    (3, 3, 64): ("head_major", 1),    # odd head count at hd 64
    (4, 2, 128): ("lane_dense", 1),   # GQA at hd 128, as starcoder2
    (4, 4, 32): ("lane_dense", 4),
}


@pytest.mark.parametrize("S,H,KV,hd", [(64, 2, 2, 32), (128, 4, 2, 64),
                                       (96, 4, 1, 16), (256, 4, 4, 64),
                                       (256, 3, 3, 64), (256, 4, 2, 128),
                                       (256, 4, 4, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes_dtypes(S, H, KV, hd, dtype):
    B = 2
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, hd), dtype)
    assert _plan(q.shape, k.shape) == FLASH_PLANS[(H, KV, hd)]
    ref = fa_ref.naive_attention(q, k, v, causal=True, window=0)
    pal = flash_attention_tpu(q, k, v, causal=True, block_q=128, block_k=32,
                              interpret=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(pal, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("window,softcap,causal", [(16, 0.0, True),
                                                   (0, 20.0, True),
                                                   (32, 30.0, True),
                                                   (0, 0.0, False)])
def test_flash_attention_masks(window, softcap, causal):
    B, S, H, hd = 1, 128, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (jax.random.normal(ks[i], (B, S, H, hd)) for i in range(3))
    kw = dict(causal=causal, window=window, logit_softcap=softcap)
    ref = fa_ref.naive_attention(q, k, v, **kw)
    blk = fa_ref.blocked_attention(q, k, v, block_k=32, **kw)
    pal = flash_attention_tpu(q, k, v, block_q=32, block_k=32, interpret=True,
                              **kw)
    np.testing.assert_allclose(np.asarray(blk), np.asarray(ref), atol=2e-5)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref), atol=2e-5)


def _grad_case(Sq, Sk, H, KV, causal, window, softcap, hd=16):
    """The hd-16 cases keep the ids they had before hd was a column."""
    return pytest.param(Sq, Sk, H, KV, causal, window, softcap, hd,
                        id="-".join(map(str, (Sq, Sk, H, KV, causal, window,
                                              softcap)))
                        if hd == 16 else None)


@pytest.mark.parametrize("Sq,Sk,H,KV,causal,window,softcap,hd", [
    _grad_case(64, 64, 4, 2, True, 0, 0.0),        # GQA causal
    _grad_case(128, 128, 2, 2, True, 16, 20.0),     # window + softcap
    _grad_case(64, 64, 2, 1, False, 0, 0.0),        # MQA, bidirectional
    _grad_case(16, 40, 2, 2, False, 0, 0.0),        # cross-attention, Sq != Sk
    _grad_case(256, 256, 4, 4, True, 0, 0.0, hd=64),      # MHA, lane-dense
    _grad_case(256, 256, 3, 3, True, 0, 0.0, hd=64),      # odd H, head-major
    _grad_case(256, 256, 4, 2, True, 0, 0.0, hd=128),     # GQA, lane-dense
    _grad_case(256, 256, 4, 2, True, 0, 0.0, hd=64),      # GQA, head-major
    _grad_case(384, 384, 4, 4, True, 100, 20.0, hd=64),   # window + softcap
    _grad_case(128, 384, 4, 4, False, 0, 0.0, hd=64),     # Sq != Sk
])
def test_flash_attention_grad_vs_ref(Sq, Sk, H, KV, causal, window, softcap,
                                     hd):
    """The custom-VJP backward (Pallas dq and dk/dv kernels) against
    autodiff through the naive oracle."""
    B = 2
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (B, Sq, H, hd))
    k = jax.random.normal(ks[1], (B, Sk, KV, hd))
    v = jax.random.normal(ks[2], (B, Sk, KV, hd))
    do = jax.random.normal(ks[3], (B, Sq, H, hd))
    assert _plan(q.shape, k.shape) == FLASH_PLANS[(H, KV, hd)]
    kw = dict(causal=causal, window=window, logit_softcap=softcap)

    def pal(q, k, v):
        return jnp.sum(flash_attention_tpu(q, k, v, block_q=128, block_k=32,
                                           interpret=True, **kw) * do)

    def ref(q, k, v):
        return jnp.sum(fa_ref.naive_attention(q, k, v, **kw) * do)

    for got, want in zip(jax.grad(pal, (0, 1, 2))(q, k, v),
                         jax.grad(ref, (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


def _pallas_calls(jaxpr, name):
    """Number of ``pallas_call`` equations called ``name`` in ``jaxpr`` and
    every jaxpr nested in its equations."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += eqn.params["name"] == name
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jax.extend.core.Jaxpr):
                    n += _pallas_calls(sub, name)
    return n


@pytest.mark.parametrize("H,KV,hd", [(4, 4, 64), (4, 2, 64)],
                         ids=["lane_dense", "head_major"])
@pytest.mark.parametrize("remat", ["nothing", "dots"])
def test_flash_attention_residuals_survive_the_layer_checkpoint(H, KV, hd,
                                                                 remat):
    """Under the layer scan's checkpoint policy the backward keeps the
    forward kernel's o and lse: the gradient holds one forward kernel (the
    primal's), and equals the gradient without a checkpoint bit for bit."""
    B, S, L = 1, 128, 2
    D = H * hd
    assert _plan((B, S, H, hd), (B, S, KV, hd)) == FLASH_PLANS[(H, KV, hd)]
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    ws = tuple(0.05 * jax.random.normal(ks[i], (L, D, n)) for i, n in
               enumerate((D, KV * hd, KV * hd, D)))
    x = jax.random.normal(ks[4], (B, S, D))

    def layer(x, w):
        wq, wk, wv, wo = w
        o = flash_attention_tpu((x @ wq).reshape(B, S, H, hd),
                                (x @ wk).reshape(B, S, KV, hd),
                                (x @ wv).reshape(B, S, KV, hd),
                                interpret=True)
        return x + o.reshape(B, S, D) @ wo, None

    def loss(ws, x, checkpointed):
        body = (jax.checkpoint(layer, policy=transformer.remat_policy(remat))
                if checkpointed else layer)
        return jnp.sum(jax.lax.scan(body, x, ws)[0] ** 2)

    grad = jax.grad(loss)
    jaxpr = jax.make_jaxpr(grad, static_argnums=2)(ws, x, True).jaxpr
    assert _pallas_calls(jaxpr, "flash_attention_fwd") == 1
    assert _pallas_calls(jaxpr, "flash_attention_dq") == 1
    assert _pallas_calls(jaxpr, "flash_attention_dkv") == 1
    for got, want in zip(grad(ws, x, True), grad(ws, x, False)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("name,plan", [
    ("gpt2-12l", ("lane_dense", 2)), ("whisper-base", ("lane_dense", 2)),
    ("starcoder2-3b", ("lane_dense", 1)), ("qwen2-vl-2b", ("lane_dense", 1)),
    ("yi-34b", ("lane_dense", 1)), ("gemma2-9b", ("lane_dense", 1)),
    ("llama3-0.3b", ("head_major", 1)), ("mixtral-0.3b", ("head_major", 1)),
])
def test_flash_attention_plan_of_configs(name, plan):
    """The layout each config's attention takes, as the kernel's docstring
    lists it."""
    cfg = cfglib.get_config(name)
    q = (1, 1024, cfg.num_heads, cfg.head_dim)
    k = (1, 1024, cfg.num_kv_heads, cfg.head_dim)
    assert _plan(q, k) == plan


def test_flash_attention_sharded_over_mesh():
    """On a multi-device mesh the kernel runs per shard (batch over
    'data', heads over 'model'); the result equals the unsharded oracle."""
    mesh = mesh_lib.make_train_mesh("4x2")
    B, S, H, hd = 4, 64, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q, k, v = (jax.random.normal(ks[i], (B, S, H, hd)) for i in range(3))
    prev = common.get_active_mesh()
    common.set_active_mesh(mesh)
    try:
        out = jax.jit(lambda q, k, v: fa_ops.flash_attention_sharded(
            q, k, v, interpret=True))(q, k, v)
    finally:
        common.set_active_mesh(prev)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(fa_ref.naive_attention(q, k, v)),
                               atol=2e-5)


def test_blocked_attention_cross_ragged():
    """Cross-attention path: Sq != Sk, Sk not a multiple of block size."""
    B, Sq, Sk, H, hd = 2, 16, 50, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, hd))
    k = jax.random.normal(ks[1], (B, Sk, H, hd))
    v = jax.random.normal(ks[2], (B, Sk, H, hd))
    ref = fa_ref.naive_attention(q, k, v, causal=False)
    blk = fa_ref.blocked_attention(q, k, v, causal=False, block_k=16)
    np.testing.assert_allclose(np.asarray(blk), np.asarray(ref), atol=2e-5)


# ---------------------------------------------------------------------------
# paged attention
# ---------------------------------------------------------------------------

def _paged_case(seed, B, H, KV, hd, bs, NB, spare=3):
    """Random pool + permuted block tables + ragged cursors.  NP includes
    spare pages so tables exercise non-identity physical placement."""
    NP = B * NB + spare
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, 1, H, hd))
    kp = jax.random.normal(ks[1], (NP, bs, KV, hd))
    vp = jax.random.normal(ks[2], (NP, bs, KV, hd))
    rng = np.random.default_rng(seed)
    tbl = jnp.asarray(rng.permutation(NP)[:B * NB].reshape(B, NB), jnp.int32)
    idx = jnp.asarray(rng.integers(0, NB * bs, (B,)), jnp.int32)
    return q, kp, vp, tbl, idx


@pytest.mark.parametrize("H,KV,hd,bs,NB", [(4, 2, 16, 8, 4), (2, 2, 32, 16, 2),
                                           (8, 2, 8, 4, 6)])
def test_paged_attention_kernel_vs_ref(H, KV, hd, bs, NB):
    q, kp, vp, tbl, idx = _paged_case(0, 3, H, KV, hd, bs, NB)
    ref = pa_ref.paged_attention_ref(q, kp, vp, tbl, idx)
    pal = paged_attention_tpu(q, kp, vp, tbl, idx, interpret=True)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


def test_paged_attention_kernel_softcap_and_edge_cursors():
    q, kp, vp, tbl, _ = _paged_case(1, 2, 4, 4, 16, 8, 4)
    for idx in ([0, 0], [31, 7]):            # first slot only / full + ragged
        idx = jnp.asarray(idx, jnp.int32)
        ref = pa_ref.paged_attention_ref(q, kp, vp, tbl, idx,
                                         logit_softcap=20.0)
        pal = paged_attention_tpu(q, kp, vp, tbl, idx, logit_softcap=20.0,
                                  interpret=True)
        np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


def test_paged_ref_matches_contiguous_gather():
    """The gather path == masked attention over the logically contiguous
    layout (same math the contiguous decode uses, by construction)."""
    q, kp, vp, tbl, idx = _paged_case(2, 2, 4, 2, 16, 8, 4)
    S = tbl.shape[1] * kp.shape[1]
    k = pa_ref.gather_pages(kp, tbl)
    v = pa_ref.gather_pages(vp, tbl)
    valid = (jnp.arange(S)[None, :] <= idx[:, None])[:, None, :]
    want = pa_ref.masked_gqa_attention(q, k, v, valid)
    got = pa_ref.paged_attention_ref(q, kp, vp, tbl, idx)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# newton-schulz
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(32, 64), (64, 32), (128, 128), (96, 40)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_newton_schulz_vs_ref(shape, dtype):
    m = jax.random.normal(jax.random.PRNGKey(0), shape, dtype)
    ref = newton_schulz_ref(m)
    pal = ns_ops.newton_schulz_pallas(m, interpret=True)
    tol = 3e-2 if dtype == jnp.bfloat16 else 5e-5
    np.testing.assert_allclose(np.asarray(pal, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


def test_newton_schulz_orthogonalizes():
    m = jax.random.normal(jax.random.PRNGKey(1), (64, 128))
    y = ns_ops.newton_schulz_pallas(m, interpret=True)
    s = jnp.linalg.svd(y, compute_uv=False)
    assert float(s.max()) < 1.35 and float(s.min()) > 0.3


def test_tiled_matmul():
    x = jax.random.normal(jax.random.PRNGKey(2), (256, 384))
    y = jax.random.normal(jax.random.PRNGKey(3), (384, 128))
    out = ns_kernel.matmul(x, y, bm=128, bk=128, bn=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x @ y),
                               atol=1e-3, rtol=1e-4)


def test_tiled_matmul_default_tiles_cover_every_k():
    """K = 768 does not divide the default 512 k-tile: the tile shrinks to
    one that divides, so no slice of the contraction is dropped."""
    x = jax.random.normal(jax.random.PRNGKey(4), (256, 768))
    y = jax.random.normal(jax.random.PRNGKey(5), (768, 384))
    out = ns_kernel.matmul(x, y, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x @ y),
                               atol=1e-3, rtol=1e-4)


def test_newton_schulz_tiled_path_vs_ref():
    """The large-matrix path (tiled matmuls) against the oracle."""
    m = jax.random.normal(jax.random.PRNGKey(6), (128, 384))
    out = ns_ops._ns_tiled(m, 5, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(newton_schulz_ref(m)), atol=5e-5)


def _muon_products():
    """Every tiled product of the Muon matrices of ``gpt2-12l`` and
    ``gpt2-60l`` (sides 768, 3072, 12288 and the 50304 vocabulary), each
    matrix in both orientations: NS works on x with its short side n
    first, and forms x·xᵀ (n, m, n), its square (n, n, n) and poly·x
    (n, n, m)."""
    sides = [(768, 768), (768, 3072), (768, 50304), (3072, 3072),
             (3072, 12288), (3072, 50304)]
    out = []
    for a, b in sides:
        for shape in {(a, b), (b, a)}:
            n, m = sorted(shape)
            for kind, mkn in (("gram", (n, m, n)), ("square", (n, n, n)),
                              ("poly_x", (n, n, m))):
                out.append(pytest.param(mkn, id=f"{shape[0]}x{shape[1]}-"
                                        f"{kind}"))
    return out


@pytest.mark.parametrize("mkn", _muon_products())
def test_matmul_plan(mkn):
    """The tiled kernel's blocks: bk divides K, blocks are lane-aligned and
    cover M and N with less than 128 wasted per block, the blocks bring at
    least the v5e ridge's 240 FLOP per fetched byte wherever M and N are
    at least 1024, and their VMEM fits the limit the kernel asks for."""
    M, K, N = mkn
    bm, bk, bn = ns_kernel._plan(M, K, N)
    assert K % bk == 0 and bk % 128 == 0
    for dim, b in ((M, bm), (N, bn)):
        assert b % 128 == 0
        blocks = -(-dim // b)
        assert blocks * b - dim < 128 * blocks
    if M >= 1024 and N >= 1024:
        assert bm * bn / (2 * (bm + bn)) >= 240
    assert ns_kernel.matmul_vmem_bytes(bm, bk, bn) <= \
        ns_kernel.MATMUL_VMEM_LIMIT


@pytest.mark.parametrize("M,K,N,transpose_rhs", [
    (256, 768, 2176, False),     # N = 17·128: a partial last block
    (2176, 768, 2176, True),     # the Gram form, x·xᵀ, partial in M and N
])
def test_tiled_matmul_planned_tiles(M, K, N, transpose_rhs):
    """At its planned tiles the kernel computes x @ y (or x @ x.T), with
    edge blocks whose rows and columns past the array are discarded."""
    x = jax.random.normal(jax.random.PRNGKey(7), (M, K))
    if transpose_rhs:
        y, want = x, x @ x.T
    else:
        y = jax.random.normal(jax.random.PRNGKey(8), (K, N))
        want = x @ y
    bm, _, bn = ns_kernel._plan(M, K, N)
    assert N % bn and (not transpose_rhs or M % bm)
    out = ns_kernel.matmul(x, y, transpose_rhs=transpose_rhs, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-3, rtol=1e-4)


def test_newton_schulz_tiled_path_spans_blocks_vs_ref():
    """The tiled path at a shape of several blocks in every dimension of
    every product (x 2176 × 2304: two blocks, the last partial, in the
    Gram's M and N, nine in its K), against the oracle."""
    m = jax.random.normal(jax.random.PRNGKey(9), (2176, 2304))
    out = ns_ops._ns_tiled(m, 5, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(newton_schulz_ref(m)), atol=5e-5)


# ---------------------------------------------------------------------------
# rwkv6 wkv
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,H,hd,chunk", [(32, 1, 16, 8), (64, 2, 16, 16),
                                          (48, 2, 32, 16)])
def test_wkv_vs_ref(S, H, hd, chunk):
    B = 2
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    r, k, v = (jax.random.normal(ks[i], (B, S, H, hd)) for i in range(3))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, S, H, hd))) * 0.5 + 0.45
    u = jax.random.normal(ks[4], (H, hd)) * 0.1
    s0 = jnp.zeros((B, H, hd, hd))
    y_ref, sf_ref = wkv_ref(r, k, v, w, u, s0)
    y_pal, sf_pal = wkv_tpu(r, k, v, w, u, s0, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(sf_pal), np.asarray(sf_ref),
                               atol=1e-4, rtol=1e-4)


def test_wkv_nonzero_initial_state():
    B, S, H, hd = 1, 16, 1, 16
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    r, k, v = (jax.random.normal(ks[i], (B, S, H, hd)) for i in range(3))
    w = jnp.full((B, S, H, hd), 0.9)
    u = jnp.zeros((H, hd))
    s0 = jax.random.normal(ks[4], (B, H, hd, hd))
    y_ref, _ = wkv_ref(r, k, v, w, u, s0)
    y_pal, _ = wkv_tpu(r, k, v, w, u, s0, chunk=8, interpret=True)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# mamba selective scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,d,N,chunk,bd", [(32, 16, 4, 8, 8),
                                            (64, 32, 8, 16, 16),
                                            (16, 8, 2, 16, 8)])
def test_selective_scan_vs_ref(S, d, N, chunk, bd):
    B = 2
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    u = jax.random.normal(ks[0], (B, S, d))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, d)))
    A = -jnp.exp(jax.random.normal(ks[2], (d, N)))
    Bm = jax.random.normal(ks[3], (B, S, N))
    Cm = jax.random.normal(ks[4], (B, S, N))
    Dp = jnp.ones((d,))
    y_ref, _ = selective_scan_ref(u, dt, A, Bm, Cm, Dp)
    y_pal = selective_scan_tpu(u, dt, A, Bm, Cm, Dp, chunk=chunk, block_d=bd,
                               interpret=True)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               atol=1e-5, rtol=1e-4)
