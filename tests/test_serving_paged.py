"""Paged KV cache + chunked prefill on the serve engine.

The paged engine must be a *numerical no-op* relative to contiguous solo
generation: with K/V living in a shared page pool addressed through block
tables, prompts prefilled in power-of-two chunks, admission gated on block
commitments, and the scheduler double-buffering its host fetch, every
request's greedy tokens are byte-identical to running it alone through the
contiguous ``ServeEngine.generate`` — on a 1x1 mesh, on the 8-device mesh,
and through a ``copying_zeroL`` depth expansion.  Structurally: the block
pool's free-list invariants hold under Poisson arrival/EOS churn
(hypothesis fuzz), free-on-EOS reclaims pages for later admissions, and
the per-length B=1 prefill executable cache is LRU-bounded.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, SSMConfig
from repro.core import expansion as exp
from repro.distributed import sharding as shd
from repro.launch import mesh as mesh_lib
from repro.models import registry
from repro.train.faults import FaultError, FaultPlane
from repro.train.kv_pool import KVBlockPool, PoolExhausted
from repro.train.radix_cache import RadixCache
from repro.train.serve_engine import ServeEngine, pow2_chunks
from repro.train.serve_scheduler import ContinuousScheduler, Request

CFG_DENSE = ModelConfig(name="pg-dense", family="dense", num_layers=4,
                        d_model=32, num_heads=4, num_kv_heads=2, d_ff=64,
                        vocab_size=64, max_seq_len=64)
CFG_WINDOW = dataclasses.replace(CFG_DENSE, name="pg-window",
                                 window_pattern=(4, 0))
CFG_MAMBA = ModelConfig(name="pg-mamba", family="ssm", num_layers=4,
                        d_model=32, num_heads=4, num_kv_heads=4, d_ff=64,
                        vocab_size=64, max_seq_len=64, attention="none",
                        position="none", block_pattern=("mamba",),
                        ssm=SSMConfig(d_state=4))
CFG_RWKV = ModelConfig(name="pg-rwkv", family="ssm", num_layers=4,
                       d_model=32, num_heads=4, num_kv_heads=4, d_ff=64,
                       vocab_size=64, max_seq_len=64, attention="none",
                       position="none", norm="layernorm",
                       block_pattern=("rwkv",),
                       ssm=SSMConfig(kind="rwkv6", head_dim=16))
CFG_MLA = dataclasses.replace(CFG_DENSE, name="pg-mla", attention="mla",
                              mla_kv_lora_rank=8)
ARCH_CFGS = {"dense": CFG_DENSE, "window": CFG_WINDOW, "mamba": CFG_MAMBA,
             "rwkv": CFG_RWKV, "mla": CFG_MLA}

REQ_SHAPES = ((5, 7), (9, 4), (3, 10), (6, 2), (4, 8), (7, 5), (2, 6),
              (8, 3))


def _params(cfg, seed=0):
    return registry.get_model(cfg).init(jax.random.PRNGKey(seed), cfg)


def _requests(cfg, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(0, cfg.vocab_size,
                                        (p,)).astype(np.int32),
                    max_new_tokens=g) for p, g in REQ_SHAPES]


def _assert_solo_parity(cfg, engine, requests, results):
    solo = ServeEngine(cfg, engine.params,
                       mesh=mesh_lib.single_device_mesh(), max_len=48)
    for req, res in zip(requests, results):
        want = solo.generate(req.prompt[None, :], req.max_new_tokens).tokens
        np.testing.assert_array_equal(res.tokens, want[0])
        assert len(res.new_tokens) == req.max_new_tokens


# ---------------------------------------------------------------------------
# Paged + chunked == contiguous solo, byte for byte
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", list(ARCH_CFGS))
def test_paged_matches_solo_single_device(arch):
    """Tight pool (8 pages of 4 tokens — admission must wait on
    free-on-EOS), chunk_len 4, max_batch 2: tokens still byte-identical to
    contiguous solo generation."""
    cfg = ARCH_CFGS[arch]
    eng = ServeEngine(cfg, _params(cfg), max_len=48, paged=True,
                      block_size=4)
    reqs = _requests(cfg)
    sched = ContinuousScheduler(eng, max_batch=2, chunk_len=4, num_blocks=8)
    _assert_solo_parity(cfg, eng, reqs, sched.run(reqs))


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["dense", "window"])
def test_paged_matches_solo_mesh8(arch):
    """Same parity on the 8-device data-parallel mesh (max_batch 4)."""
    cfg = ARCH_CFGS[arch]
    eng = ServeEngine(cfg, _params(cfg),
                      mesh=mesh_lib.make_train_mesh("host"), max_len=48,
                      paged=True, block_size=4)
    reqs = _requests(cfg)
    results = ContinuousScheduler(eng, max_batch=4, chunk_len=4).run(reqs)
    _assert_solo_parity(cfg, eng, reqs, results)


@pytest.mark.slow
def test_paged_serves_expanded_checkpoint_identically():
    """copying_zeroL 2->4 expansion served PAGED produces the identical
    token stream as the pre-expansion params served contiguous solo (the
    paper's drop-in-continuation claim survives the cache redesign)."""
    cfg2, cfg4 = CFG_DENSE.with_depth(2), CFG_DENSE.with_depth(4)
    p2 = _params(cfg2, seed=1)
    p4 = exp.expand_params(p2, cfg2, 4, "copying_zeroL")
    reqs = _requests(cfg2)[:4]
    eng4 = ServeEngine(cfg4, p4, max_len=48, paged=True, block_size=4)
    results = ContinuousScheduler(eng4, max_batch=2, chunk_len=4).run(reqs)
    solo2 = ServeEngine(cfg2, p2, mesh=mesh_lib.single_device_mesh(),
                        max_len=48)
    for req, res in zip(reqs, results):
        want = solo2.generate(req.prompt[None, :], req.max_new_tokens).tokens
        np.testing.assert_array_equal(res.tokens, want[0])


@pytest.mark.parametrize("overlap", [True, False])
def test_overlap_is_a_numerical_noop(overlap):
    """Dispatch-then-fetch double buffering changes WHEN the host observes
    termination, never what any request decodes."""
    cfg = CFG_DENSE
    eng = ServeEngine(cfg, _params(cfg), max_len=48, paged=True,
                      block_size=4)
    reqs = _requests(cfg)
    results = ContinuousScheduler(eng, max_batch=2, chunk_len=4,
                                  overlap=overlap).run(reqs)
    _assert_solo_parity(cfg, eng, reqs, results)


def test_chunk_widths_and_eos_free():
    """Chunk widths are the binary decomposition (compile count is
    O(log max_len)); EOS mid-budget frees pages immediately and the
    follow-up request is served in the reclaimed slot."""
    assert pow2_chunks(13) == [8, 4, 1]
    assert pow2_chunks(13, cap=4) == [4, 4, 4, 1]
    assert pow2_chunks(1) == [1]
    assert pow2_chunks(20, cap=7) == [4, 4, 4, 4, 4]

    cfg = CFG_DENSE
    eng = ServeEngine(cfg, _params(cfg), max_len=48, paged=True,
                      block_size=4)
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
    solo = ServeEngine(cfg, eng.params, mesh=mesh_lib.single_device_mesh(),
                       max_len=48)
    stream = solo.generate(prompt[None, :], 12).tokens[0, 6:]
    eos = int(stream[4])
    cut = int(np.argmax(stream == eos)) + 1
    other = Request(prompt=rng.integers(0, cfg.vocab_size,
                                        (4,)).astype(np.int32),
                    max_new_tokens=5)
    sched = ContinuousScheduler(eng, max_batch=1, eos_id=eos, num_blocks=5)
    results = sched.run([Request(prompt=prompt, max_new_tokens=12), other])
    assert results[0].finish_reason == "eos"
    np.testing.assert_array_equal(results[0].new_tokens, stream[:cut])
    assert results[1].slot == results[0].slot == 0
    assert len(results[1].new_tokens) >= 1


# ---------------------------------------------------------------------------
# Block pool: alloc/free invariants under Poisson arrival/EOS churn
# ---------------------------------------------------------------------------


def test_pool_admission_contract():
    pool = KVBlockPool(num_blocks=8, block_size=4, batch=4, max_blocks=8)
    assert pool.blocks_needed(5, 7) == 3                 # ceil(12/4)
    pool.admit(0, 5, 7)
    assert pool.committed_blocks == 3 and pool.allocated_blocks == 0
    pool.advance(0, 5)                                   # prompt pages
    assert pool.allocated_blocks == 2
    with pytest.raises(PoolExhausted):
        pool.advance(0, 13)                              # beyond commitment
    pool.admit(1, 16, 4)                                 # 5 pages -> 8 total
    with pytest.raises(PoolExhausted):
        pool.admit(2, 4, 4)                              # 2 more: over 8
    pool.free(0)
    assert pool.committed_blocks == 5 and pool.free_blocks == 8
    pool.admit(2, 4, 4)                                  # fits now
    pool.check_invariants()


def test_pool_truncate_row_contract():
    """Speculative rollback: ``truncate_row`` releases pages past the
    rewound cursor while the commitment stays, freed pages are reusable
    (by the same row's re-advance AND by other rows), truncation at/above
    the frontier is a no-op, and double truncation can never double-free."""
    pool = KVBlockPool(num_blocks=8, block_size=4, batch=4, max_blocks=8)
    pool.admit(0, 5, 11)                                 # 4-page commitment
    pool.advance(0, 12)                                  # speculate ahead
    assert pool.allocated_blocks == 3
    pages_before = list(pool._rows[0])
    assert pool.truncate_row(0, 6)                       # rollback to 6 toks
    assert pool.allocated_blocks == 2
    assert pool.committed_blocks == 4                    # commitment intact
    assert (pool.table[0, 2:] == pool.trash).all()
    pool.check_invariants()
    assert not pool.truncate_row(0, 6)                   # idempotent
    assert not pool.truncate_row(0, 8)                   # at the frontier
    pool.check_invariants()
    pool.advance(0, 12)                                  # re-advance works
    assert pool.allocated_blocks == 3
    assert pool.table[0, 2] == pages_before[2]           # LIFO: same page
    pool.truncate_row(0, 0)                              # full rollback
    assert pool.allocated_blocks == 0 and pool.free_blocks == 8
    pool.check_invariants()
    # freed pages are admissible/allocatable by OTHER rows
    pool.admit(1, 12, 4)
    pool.advance(1, 16)
    assert pool.allocated_blocks == 4
    pool.check_invariants()
    with pytest.raises(ValueError):
        pool.truncate_row(2, 4)                          # not admitted
    with pytest.raises(ValueError):
        pool.truncate_row(0, -1)
    pool.free(0)
    with pytest.raises(ValueError):
        pool.truncate_row(0, 2)                          # freed row
    pool.check_invariants()


def _drive_pool(events, num_blocks):
    """Shared fuzz driver: admit/advance/speculate-rollback/EOS churn.

    Each event is ``(row, prompt, budget, eos_after, spec)``; ``spec > 0``
    interleaves speculative lookahead (advance ``spec`` tokens ahead) with
    ``truncate_row`` rollback at every spec-th token — the PR 5 cycle.
    Properties: pages never leak or double-book, commitments bound
    allocation, admitted rows' advances never fail (no-preemption), and a
    drained pool returns to fully free / zero commitment."""
    pool = KVBlockPool(num_blocks=num_blocks, block_size=4, batch=6,
                       max_blocks=8)
    live = {}
    for row, p, g, e, spec in events:
        if row in live:                  # EOS: free mid-flight
            pool.free(row)
            del live[row]
            pool.check_invariants()
            continue
        need = pool.blocks_needed(p, g)
        if need > min(pool.num_blocks, pool.max_blocks) \
                or not pool.can_admit(need):
            continue
        pool.admit(row, p, g)
        tokens = min(p + max(0, g - 1 - e), p + g - 1)
        for t in range(1, tokens + 1):   # alloc-on-advance, token by token
            if spec and t % spec == 0:   # speculate γ ahead, roll back
                pool.advance(row, min(t + spec, p + g - 1))
                pool.truncate_row(row, t)
                pool.check_invariants()
            pool.advance(row, t)         # must never raise
        live[row] = True
        pool.check_invariants()
    for row in live:
        pool.free(row)
    pool.check_invariants()
    assert pool.free_blocks == pool.num_blocks
    assert pool.committed_blocks == 0


try:
    import hypothesis                              # noqa: F401
    HAVE_HYPOTHESIS = True
except ImportError:                                # pragma: no cover
    HAVE_HYPOTHESIS = False


def test_pool_fuzz_poisson_arrivals_and_eos():
    """Random admit/advance/speculate/EOS churn against the pool contract
    (see ``_drive_pool``).  Runs under hypothesis when installed (declared
    in requirements-test.txt); otherwise a seeded generator drives the
    SAME property over 60 random event tapes — the fuzz never silently
    skips (test.sh surfaces which generator ran)."""
    if HAVE_HYPOTHESIS:
        from hypothesis import given, settings, strategies as st

        @settings(max_examples=40, deadline=None)
        @given(st.lists(st.tuples(st.integers(0, 5),      # event row
                                  st.integers(1, 14),     # prompt len
                                  st.integers(1, 10),     # budget
                                  st.integers(0, 9),      # EOS after e toks
                                  st.integers(0, 4)),     # spec lookahead γ
                        min_size=1, max_size=60),
               st.integers(2, 12))
        def run(events, num_blocks):
            _drive_pool(events, num_blocks)

        run()
    else:
        rng = np.random.default_rng(0)
        for _ in range(60):
            events = [(int(rng.integers(0, 6)), int(rng.integers(1, 15)),
                       int(rng.integers(1, 11)), int(rng.integers(0, 10)),
                       int(rng.integers(0, 5)))
                      for _ in range(int(rng.integers(1, 61)))]
            _drive_pool(events, int(rng.integers(2, 13)))


def _drive_pool_prefix(events, num_blocks, carryless=True, quantized=False,
                       faulted=False):
    """Fuzz the refcount/COW/pin surface: a real ``RadixCache`` over the
    pool, prompts drawn from a 2-token alphabet so prefixes collide
    constantly.  Each event ``(row, p, tseed, g, e, spec, deep)``
    interleaves prefix-hit admission (shared page mapping, exact-boundary
    copy-on-write), publish (tree pins), speculative rollback
    (``truncate_row`` at every spec-th decode token — the PR 5 cycle, now
    interleaved with live prefix shares), ``deep``-truncation below the
    shared boundary, free-with-refs, and LRU eviction whenever the free
    list runs dry.  ``carryless=False`` drives the window/recurrent
    publish-and-match surface instead of the dense one: publishers attach
    a carry snapshot at the last page boundary below P, matchers clamp to
    snapshot-bearing nodes (asserting the restored carry's extent equals
    the skip), and inadmissible hits re-clamp shallower exactly like the
    scheduler.  ``check_invariants`` after every op asserts refcount ==
    table refs + tree pins, no shared page on the free list, and the
    starvation guarantee; COW is additionally checked to never touch a
    page with other references.

    ``quantized=True`` additionally models the int8/fp8 pool's scale
    arrays as host payloads keyed by PHYSICAL page id — exactly how the
    engine stores them — (re)written whenever a page is allocated to a
    row, copied on the COW clone (the ``make_page_copy_step`` contract).
    Every prefix-hit admission then asserts each matched page's payload
    still equals the content fingerprint its shared prefix implies: any
    page-reuse path (free, LRU eviction, truncate_row release, COW) that
    let a physical page reach a new row without its scale state following
    would trip it.

    ``faulted=True`` arms a seeded Bernoulli fault storm on the pool's
    ``pool.alloc`` / ``pool.evict`` / ``radix.match`` / ``radix.publish``
    sites (``train.faults``) and mirrors the scheduler's containment:
    every faulted op is retried after freeing any half-admission, with
    the FULL invariant audit (pool refcounts + radix pin counts) run at
    every injected fault — proving that sites firing before mutation
    make bounded retry exact and that no fault path leaks a page.  The
    lane also drops the cold-admission capacity precheck, so the natural
    ``PoolExhausted`` path is exercised under the same audit."""
    pool = KVBlockPool(num_blocks=num_blocks, block_size=4, batch=6,
                       max_blocks=8,
                       faults=FaultPlane.seeded(0.05, seed=1)
                       if faulted else None)
    radix = RadixCache(pool)
    live = {}
    scales = {}                      # physical page -> modeled scale payload

    def check():
        pool.check_invariants()
        radix.check_invariants()

    def retry(fn, row=None):
        """Scheduler-mirror containment: on an injected fault, undo any
        half-admission (free the committed row), audit, retry — the
        sites fire before state moves, so the retry is exact."""
        for _ in range(16):
            try:
                return fn()
            except FaultError:
                if row is not None and row in pool._commit:
                    pool.free(row)
                check()
        raise AssertionError("seeded fault storm exceeded the retry budget")

    def _fp(prompt, idx):            # content fingerprint of a FULL page
        bs = pool.block_size
        return ("prefix", tuple(prompt[idx * bs:(idx + 1) * bs].tolist()))

    def _advance(row, prompt, p, t):
        """pool.advance + the quantize-at-write model: pages newly
        allocated to this row get payloads from what the engine would
        write there (prompt fingerprints for full prompt pages, a private
        decode marker past them)."""
        before = set(pool.row_pages(row)) if quantized else None
        retry(lambda: pool.advance(row, t))
        if quantized:
            for i, pg in enumerate(pool.row_pages(row)):
                if pg not in before:
                    scales[pg] = (_fp(prompt, i)
                                  if (i + 1) * pool.block_size <= p
                                  else ("decode", row))
    for row, p, tseed, g, e, spec, deep in events:
        if row in live:                  # EOS while shared/pinned: pages
            pool.free(row)               # with other references survive
            del live[row]
            check()
            continue
        prompt = np.random.default_rng(tseed).integers(
            0, 2, size=p).astype(np.int32)
        need = pool.blocks_needed(p, g)
        if need > min(pool.num_blocks, pool.max_blocks):
            continue
        limit = p + g - 1

        def _match():
            m = radix.match(prompt, carryless=carryless)
            while m is not None and not pool.can_admit_prefix(
                    need, m.pages, m.cow_last):
                # scheduler-mirror: re-clamp an inadmissible hit shallower
                m = radix.match(prompt, carryless=carryless,
                                max_pages=len(m.pages) - 1)
            return m
        match = retry(_match)
        if match is not None:
            if not carryless:
                # carry matches clamp to a snapshot node: the restored
                # carry was taken at exactly ``skip`` tokens
                assert match.carry["extent"] == match.skip
                assert match.skip <= p - 1
            if quantized:
                # scale state rode every reuse of these physical pages:
                # the payload still matches the shared prefix content
                for i, pg in enumerate(match.pages):
                    assert scales[pg] == _fp(prompt, i)
            def _admit_hit():
                baseline = {pg: pool.ref_count(pg) for pg in match.pages}
                return baseline, pool.admit_prefix(row, p, g, match.pages,
                                                   match.cow_last)
            refs, cow = retry(_admit_hit, row=row)
            if match.cow_last:
                src, dst = cow
                # COW never mutates a shared page: the source keeps its
                # OTHER references; the row gets a fresh private clone.
                assert src == match.pages[-1] and dst != src
                assert pool.ref_count(src) == refs[src]
                assert pool.ref_count(dst) == 1
                if quantized:    # the page-copy step clones scales too
                    scales[dst] = scales[src]
            start = match.skip
        elif faulted or pool.can_admit(need):
            # faulted lane: no capacity precheck — a clean PoolExhausted
            # reject (the scheduler's admission-gate path) must leave the
            # pool exactly as it was
            try:
                retry(lambda: pool.admit(row, p, g), row=row)
            except PoolExhausted:
                check()
                continue
            start = 0
        else:
            continue
        check()
        _advance(row, prompt, p, p)      # tail prefill (never exhausts)
        n_pub = p // pool.block_size
        if n_pub and carryless:
            retry(lambda: radix.publish(
                prompt, pool.row_pages(row)[:n_pub], n_pub))
        elif n_pub:
            # window/recurrent publishers: carry snapshot at the last page
            # boundary at/below P-1 (what ServeEngine.begin_prefill does)
            snap_at = ((p - 1) // pool.block_size) * pool.block_size
            retry(lambda: radix.publish(
                prompt, pool.row_pages(row)[:n_pub], n_pub,
                carry={"extent": snap_at} if snap_at else None,
                carry_tokens=snap_at))
        check()
        tokens = min(p + max(0, g - 1 - e), limit)
        for t in range(p + 1, tokens + 1):
            if spec and t % spec == 0:   # speculate ahead, roll back
                _advance(row, prompt, p, min(t + spec, limit))
                pool.truncate_row(row, t)
                check()
            _advance(row, prompt, p, t)
        if deep and start:               # rollback BELOW the shared
            pool.truncate_row(row, max(0, start - 2))   # boundary: legal at
            check()                      # pool level (refs drop, pinned
            _advance(row, prompt, p, tokens)   # pages survive; fresh pages
            # back the re-advance (rewritten, so their scales rewrite too)
        live[row] = True
        check()
    for row in live:
        pool.free(row)
    check()
    while radix.evict_one():             # drain the tree, LRU-leaf-first
        check()
    assert radix.num_nodes == 0          # all pins released...
    assert pool.free_blocks == pool.num_blocks   # ...and all pages freed
    assert pool.committed_blocks == 0


@pytest.mark.parametrize("carryless,quantized,faulted",
                         [(True, False, False), (False, False, False),
                          (True, True, False), (True, False, True)],
                         ids=["dense", "carry", "quantized", "faulted"])
def test_pool_fuzz_prefix_share_cow_evict(carryless, quantized, faulted):
    """Random share/COW/publish/evict churn — with spec truncate_row
    rollbacks interleaved — against the refcounted pool + radix tree
    contract (see ``_drive_pool_prefix``); the ``carry`` lane drives the
    window/recurrent snapshot publish-and-clamp surface, the
    ``quantized`` lane the page-keyed scale-state model.  Hypothesis when
    installed, else 60 seeded event tapes over the same property."""
    if HAVE_HYPOTHESIS:
        from hypothesis import given, settings, strategies as st

        @settings(max_examples=40, deadline=None)
        @given(st.lists(st.tuples(st.integers(0, 5),      # event row
                                  st.integers(1, 14),     # prompt len
                                  st.integers(0, 7),      # prompt content
                                  st.integers(1, 10),     # budget
                                  st.integers(0, 9),      # EOS after e toks
                                  st.integers(0, 4),      # spec lookahead γ
                                  st.booleans()),         # deep truncate
                        min_size=1, max_size=60),
               st.integers(2, 12))
        def run(events, num_blocks):
            _drive_pool_prefix(events, num_blocks, carryless=carryless,
                               quantized=quantized, faulted=faulted)

        run()
    else:
        rng = np.random.default_rng(0)
        for _ in range(60):
            events = [(int(rng.integers(0, 6)), int(rng.integers(1, 15)),
                       int(rng.integers(0, 8)), int(rng.integers(1, 11)),
                       int(rng.integers(0, 10)), int(rng.integers(0, 5)),
                       bool(rng.integers(0, 2)))
                      for _ in range(int(rng.integers(1, 61)))]
            _drive_pool_prefix(events, int(rng.integers(2, 13)),
                               carryless=carryless, quantized=quantized,
                               faulted=faulted)


# ---------------------------------------------------------------------------
# Pool sharding: pages replicated over DP, block table addressable anywhere
# ---------------------------------------------------------------------------


def test_page_pool_sharding_never_splits_pages_over_data():
    mesh = mesh_lib.make_train_mesh("host")
    specs = {
        "layer0": {"k_pages": jax.ShapeDtypeStruct((2, 16, 8, 2, 8),
                                                   jnp.float32),
                   "v_pages": jax.ShapeDtypeStruct((2, 16, 8, 2, 8),
                                                   jnp.float32)},
        "layer1": {"k": jax.ShapeDtypeStruct((2, 8, 16, 2, 8), jnp.float32)},
    }
    sh = shd.cache_shardings(specs, mesh)
    # pages: dim1 (16 pages, divisible by 8) must stay unsharded over data
    assert sh["layer0"]["k_pages"].spec[1] is None
    # contiguous leaf: batch dim still sharded over data as before
    assert P(sh["layer1"]["k"].spec[1]) == P("data")


# ---------------------------------------------------------------------------
# Satellite: per-length B=1 prefill executables are LRU-bounded
# ---------------------------------------------------------------------------


def test_prefill_executable_cache_is_bounded():
    cfg = CFG_DENSE
    eng = ServeEngine(cfg, _params(cfg), max_len=64, prefill_cache_size=3)
    state = eng.continuous_state(1)
    rng = np.random.default_rng(9)
    for p_len in (3, 5, 7, 9, 11, 5, 3):
        prompt = rng.integers(0, cfg.vocab_size, (p_len,)).astype(np.int32)
        state, tok, _ = eng.prefill_request(state, prompt)
    assert len(eng._prefill_lru) <= 3
    # most-recently-used lengths survive
    assert (3, False) in eng._prefill_lru and (5, False) in eng._prefill_lru


def test_mla_rank0_serves_on_dense_kv_paged_path():
    """Regression (gate keyed on rank truthiness): ``attention='mla'`` with
    ``mla_kv_lora_rank=0`` carries standard wk/wv projections everywhere
    (param init, contiguous and paged caches all key on the rank, not the
    attention name), so it must serve on the dense K/V paged path with
    byte parity — not slip through unvalidated or hit the latent path with
    a rank-0 pool."""
    cfg = dataclasses.replace(CFG_DENSE, name="pg-mla0", attention="mla",
                              mla_kv_lora_rank=0)
    params = _params(cfg)
    assert "wk" in params["blocks"]["layer0"]["attn"]    # standard proj,
    assert "wkv_a" not in params["blocks"]["layer0"]["attn"]  # no latents
    eng = ServeEngine(cfg, params, max_len=48, paged=True, block_size=4)
    cache = eng.continuous_state(2, num_blocks=8).cache
    assert "k_pages" in cache["layer0"]              # dense pool, no latents
    assert "latent_pages" not in cache["layer0"]
    reqs = _requests(cfg)[:4]
    sched = ContinuousScheduler(eng, max_batch=2, chunk_len=4, num_blocks=8)
    _assert_solo_parity(cfg, eng, reqs, sched.run(reqs))


# ---------------------------------------------------------------------------
# Quantized pool storage (kv_dtype='int8'/'fp8'): tolerance lane + structure
# ---------------------------------------------------------------------------


class TestQuantizedTolerance:
    """Quantized page storage replaces the byte-parity contract with a
    TOLERANCE lane (referenced from ``launch/serve.py --kv-dtype``): int8
    pages + per-slot-per-head f32 scales perturb attention logits, so a
    greedy stream may diverge from the f32 mirror at near-ties and then
    stay diverged (edit cascade).  The documented contract is aggregate
    per-token agreement >= QUANT_AGREEMENT against the same workload
    through an f32 pool — measured on these random-init tiny configs:
    dense 0.956, window/mla 1.0 (their quantized working set is smaller —
    rings ride the float carry, MLA quantizes rank-8 latents).  Archs with
    no paged attention layers (mamba/rwkv) quantize nothing and must stay
    byte-identical.  Everything else about the engine is dtype-invariant
    by construction — page counts, admission math, scheduler behavior —
    which the structural tests pin down."""

    QUANT_AGREEMENT = 0.9
    # fp8 e4m3 keeps 3 mantissa bits against int8's 7 significant bits, so
    # its lane is looser (measured 0.889 on the dense config).
    FP8_AGREEMENT = 0.8

    @staticmethod
    def _agreement(res_a, res_b):
        tot = hit = 0
        for a, b in zip(res_a, res_b):
            ta, tb = np.asarray(a.new_tokens), np.asarray(b.new_tokens)
            n = min(len(ta), len(tb))
            hit += int((ta[:n] == tb[:n]).sum())
            tot += max(len(ta), len(tb))
        return hit / max(tot, 1)

    def _run_pair(self, cfg, mesh=None, **kv):
        params = _params(cfg)
        reqs = _requests(cfg)
        out = []
        for kv_dtype in (None, "int8"):
            eng = ServeEngine(cfg, params, mesh=mesh, max_len=48,
                              paged=True, block_size=4, kv_dtype=kv_dtype,
                              **kv)
            out.append(ContinuousScheduler(eng, max_batch=2, chunk_len=4,
                                           num_blocks=8).run(reqs))
        return out

    @pytest.mark.parametrize("arch", ["dense", "window", "mla"])
    def test_greedy_agreement_single_device(self, arch):
        f32, i8 = self._run_pair(ARCH_CFGS[arch])
        assert self._agreement(f32, i8) >= self.QUANT_AGREEMENT
        for a, b in zip(f32, i8):            # lengths/termination invariant
            assert len(a.new_tokens) == len(b.new_tokens)
            assert a.finish_reason == b.finish_reason

    @pytest.mark.parametrize("arch", ["mamba", "rwkv"])
    def test_recurrent_rows_quantize_nothing(self, arch):
        """No paged attention layers -> no quantized leaves; the int8
        engine is byte-identical to f32 (state rides float recurrent
        rows), not merely within tolerance."""
        f32, i8 = self._run_pair(ARCH_CFGS[arch])
        for a, b in zip(f32, i8):
            np.testing.assert_array_equal(a.tokens, b.tokens)

    @pytest.mark.slow
    def test_greedy_agreement_mesh8(self):
        f32, i8 = self._run_pair(CFG_DENSE,
                                 mesh=mesh_lib.make_train_mesh("host"))
        assert self._agreement(f32, i8) >= self.QUANT_AGREEMENT

    def test_fp8_lane(self):
        cfg = CFG_DENSE
        params = _params(cfg)
        reqs = _requests(cfg)
        f32 = ServeEngine(cfg, params, max_len=48, paged=True, block_size=4)
        f8 = ServeEngine(cfg, params, max_len=48, paged=True, block_size=4,
                         kv_dtype="fp8")
        ra = ContinuousScheduler(f32, max_batch=2, chunk_len=4,
                                 num_blocks=8).run(reqs)
        rb = ContinuousScheduler(f8, max_batch=2, chunk_len=4,
                                 num_blocks=8).run(reqs)
        assert self._agreement(ra, rb) >= self.FP8_AGREEMENT

    def test_quantized_requires_paged(self):
        with pytest.raises(ValueError, match="paged"):
            ServeEngine(CFG_DENSE, _params(CFG_DENSE), max_len=48,
                        kv_dtype="int8")

    def test_pool_leaves_are_int8_with_f32_scales(self):
        """Structure: every paged K/V leaf stores int8 with a matching
        (NP+1, bs, KV, 1) float32 scale leaf; MLA pages rank-r latents
        with (NP+1, bs, 1) scales; window rings stay in the float cache
        dtype (per-row state outside the pool)."""
        eng = ServeEngine(CFG_DENSE, _params(CFG_DENSE), max_len=48,
                          paged=True, block_size=4, kv_dtype="int8")
        cache = eng.continuous_state(2, num_blocks=8).cache
        for layer in cache.values():
            assert layer["k_pages"].dtype == jnp.int8
            assert layer["v_pages"].dtype == jnp.int8
            assert layer["k_scales"].dtype == jnp.float32
            # stacked over the layer-scan dim: (..., NP+1, bs, KV, 1)
            assert layer["k_scales"].shape[-4:] == (9, 4, 2, 1)
            assert layer["v_scales"].shape[-4:] == (9, 4, 2, 1)
        mla = ServeEngine(CFG_MLA, _params(CFG_MLA), max_len=48,
                          paged=True, block_size=4, kv_dtype="int8")
        mcache = mla.continuous_state(2, num_blocks=8).cache
        for layer in mcache.values():
            assert layer["latent_pages"].dtype == jnp.int8
            assert layer["latent_scales"].dtype == jnp.float32
            assert layer["latent_scales"].shape[-3:] == (9, 4, 1)
        win = ServeEngine(CFG_WINDOW, _params(CFG_WINDOW), max_len=48,
                          paged=True, block_size=4, kv_dtype="int8")
        wcache = win.continuous_state(2, num_blocks=8).cache
        paged_layers = [l for l in wcache.values() if "k_pages" in l]
        ring_layers = [l for l in wcache.values() if "k_pages" not in l]
        assert paged_layers and ring_layers
        for layer in ring_layers:            # rings stay float
            assert all(v.dtype != jnp.int8 for v in layer.values())

    def test_kv_stats_telemetry(self):
        """Scheduler telemetry: bytes-per-cached-token ratio vs f32.  For
        CFG_DENSE (KV=2, hd=8): int8 K+V = 32 B + 16 B scales against
        128 B f32 -> exactly 0.375; an unquantized paged engine reports
        1.0 and a contiguous engine degenerates."""
        cfg = CFG_DENSE
        params = _params(cfg)
        i8 = ServeEngine(cfg, params, max_len=48, paged=True, block_size=4,
                         kv_dtype="int8")
        stats = ContinuousScheduler(i8, max_batch=2, chunk_len=4,
                                    num_blocks=8).kv_stats()
        assert stats["kv_dtype"] == "int8"
        assert stats["kv_bytes_ratio"] == pytest.approx(0.375)
        f32 = ServeEngine(cfg, params, max_len=48, paged=True, block_size=4)
        stats = ContinuousScheduler(f32, max_batch=2, chunk_len=4,
                                    num_blocks=8).kv_stats()
        assert stats["kv_bytes_ratio"] == pytest.approx(1.0)
        cont = ServeEngine(cfg, params, max_len=48)
        stats = ContinuousScheduler(cont, max_batch=2).kv_stats()
        assert stats["kv_dtype"] is None
