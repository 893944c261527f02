"""Serving CLI: mesh-sharded batched generation (true prefill + donated
sharded caches) over ``repro.train.serve_engine.ServeEngine``.

    PYTHONPATH=src python -m repro.launch.serve --arch gpt2-12l --smoke \
        --batch 4 --prompt-len 16 --gen 32 --mesh single

``--mesh`` picks the device layout (same specs as ``launch/train.py``):

    single          1x1 over the first device (default; exact single-device)
    host            all local devices on 'data' (batch-parallel decode)
    prod            the 256-chip (data, model) production mesh
    prod-multipod   the 512-chip multi-pod mesh
    AxB             explicit (data, model) shape, e.g. '4x2' on 8 devices

``--checkpoint DIR`` serves a ``ProgressiveTrainer`` checkpoint: the params
subtree is restored at the depth recorded in the checkpoint manifest (so a
depth-expanded model serves at its grown depth) and the engine places it
sharded onto the serve mesh — no optimizer state is touched.
Prefill and decode throughput are reported separately: prefill is one
compiled full-sequence forward, decode is one fused device step per token.

``--continuous`` switches to the continuous-batching scheduler
(``train/serve_scheduler``): ``--requests`` synthetic requests with varied
prompt/generation lengths and Poisson arrivals (``--rate`` req/s) are
admitted into ``--max-batch`` cache slots as rows free up; aggregate
throughput and p50/p95 time-to-first-token are reported.

``--paged`` (with ``--continuous``) serves through the block-paged KV
cache: a shared pool of ``--num-blocks`` pages of ``--block-size`` tokens
(default: full provisioning) addressed per row through block tables,
prompts prefilled ``--chunk-len`` tokens per scheduler iteration straight
into the pool, pages freed on EOS.  ``--no-overlap`` disables the
scheduler's dispatch-then-fetch double buffering (debugging).

The serving matrix is closed over the model registry: every architecture
composes with ``--paged``, ``--prefix-cache`` and ``--spec-depth`` —
dense and sliding-window attention page K/V rows, MLA pages its
compressed ``(block, kv_lora_rank)`` latent rows (up-projected inside the
paged-attention kernel), and recurrent blocks (mamba/rwkv) thread their
states as B=1 carries with per-round checkpoint rings for speculative
rollback and radix-tree carry snapshots for prefix hits.  Greedy streams
stay byte-identical to contiguous solo generation in every combination.

``--prefix-cache`` (with ``--paged``) turns on the prefix-sharing radix
cache (``train/radix_cache``): finished prompts publish their full KV
pages into a radix tree keyed by token content, later requests whose
prompts share that prefix map the pages straight into their block tables
and prefill only the unmatched tail (copy-on-write on an exact page
boundary; LRU-leaf eviction under pool pressure).  The synthetic workload
then shares a common system prefix across requests so the cache has
traffic to hit, and the run reports hit-rate / skipped-token telemetry.
``--no-prefix-cache`` (the default) serves every prompt cold.

``--kv-dtype {f32,bf16,int8,fp8}`` (int8/fp8 require ``--paged``) sets the
page pool's storage dtype.  int8/fp8 store quantized pages plus per-slot
float32 scales and dequantize inside the paged-attention read (fused into
the Pallas kernel's page loop on TPU), cutting the pool's bytes-per-token
to roughly a quarter — the same page counts admit at ~4x less memory, and
decode streams proportionally fewer HBM bytes.  THE PARITY CONTRACT
CHANGES: f32/bf16 greedy streams are byte-identical to contiguous solo
generation, while quantized streams are checked against the float mirror
as a TOLERANCE lane — same-step logits stay within the quantization noise
floor and greedy token streams agree within a documented edit rate (see
tests/test_serving_paged.py::TestQuantizedTolerance) rather than byte
parity.  Composes with ``--spec-depth`` (verify writes and rollback run
over quantized pages; spec-vs-plain parity WITHIN the quantized lane stays
exact) and ``--prefix-cache`` (scales are keyed by physical page id, so
shared radix pages carry their scales and shared quantized bytes are
identical across rows by construction).

``--spec-depth N`` (with ``--paged``) turns on SELF-SPECULATIVE decoding:
the depth-N truncation of the served model (shared embedding / final norm
/ tied head — progressive training's free draft) proposes ``--gamma``
tokens per iteration and the full model verifies them in one multi-token
forward through the block table; rejected tokens roll back by cursor
rewind + page release.  ``--draft-checkpoint DIR`` drafts with an
externally trained shallower checkpoint (restored at its manifest depth —
e.g. the pre-expansion checkpoint of the served model) instead of
truncating.  ``--age-limit S`` bounds first-fit admission starvation
(aging).  Greedy streams are byte-identical either way; the run reports
the draft acceptance rate.

Robustness flags (with ``--continuous``; see ``train/faults`` and the
scheduler's lifecycle hardening): ``--deadline-s S`` finishes any request
``deadline`` once S seconds pass from its arrival (queued or mid-decode);
``--queue-limit N`` bounds the arrived queue, shedding overflow with a
structured ``shed`` rejection; ``--retries K`` bounds retry-with-backoff
for transient faults before a request fails alone (the batch keeps
serving); ``--faults TAPE`` arms deterministic fault injection — either
an explicit tape ``site:nth[:kind]`` joined by commas (e.g.
``pool.alloc:3,engine.decode:5,sched.iter:40:crash``) or a seeded storm
``storm:rate[:seed]``; ``--snapshot-every N`` serializes host-side
in-flight state every N iteration boundaries (the crash-recovery input:
``ContinuousScheduler.restore`` re-prefills prompt + emitted tokens for
byte-identical resumed greedy streams).  The run reports per-reason
finish counts and goodput (completed tokens/s) next to raw tokens/s.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import configs as cfglib
from repro.checkpoint import checkpointer as ckpt
from repro.launch import compile_cache
from repro.launch import mesh as mesh_lib
from repro.models import registry
from repro.train import faults as faults_lib
from repro.train.serve_engine import ServeEngine
from repro.train.serve_scheduler import (ContinuousScheduler, Request,
                                         summarize)


def load_params(checkpoint_dir: str, cfg, step=None, dtype=None):
    """(params (host arrays), cfg-at-checkpoint-depth) from a
    ProgressiveTrainer checkpoint.  Placement is left to ``ServeEngine``,
    which resolves the serve-mesh shardings once — restoring sharded here
    would just re-shard a second time at engine construction."""
    if step is None:
        step = ckpt.latest_step(checkpoint_dir)
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {checkpoint_dir}")
    meta = ckpt.load_metadata(checkpoint_dir, step)
    cfg = cfg.with_depth(int(meta["num_layers"]))
    api = registry.get_model(cfg)
    kwargs = {} if dtype is None else {"dtype": dtype}
    p_struct = jax.eval_shape(lambda k: api.init(k, cfg, **kwargs),
                              jax.random.PRNGKey(0))
    params = ckpt.restore_subtree(checkpoint_dir, step, p_struct, "params")
    return params, cfg


def main(argv=None):
    """Run the CLI; returns the per-request results with ``--continuous``,
    else the ``GenerateResult``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt2-12l")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mesh", default="single",
                    help="single|host|prod|prod-multipod|AxB")
    ap.add_argument("--checkpoint", default=None,
                    help="ProgressiveTrainer checkpoint dir to serve")
    ap.add_argument("--step", type=int, default=None,
                    help="checkpoint step (default: latest)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching: admit staggered requests "
                         "into freed cache slots")
    ap.add_argument("--max-batch", type=int, default=4,
                    help="decode slots for --continuous")
    ap.add_argument("--requests", type=int, default=8,
                    help="synthetic requests for --continuous")
    ap.add_argument("--rate", type=float, default=20.0,
                    help="Poisson arrival rate (req/s) for --continuous")
    ap.add_argument("--eos", type=int, default=-1,
                    help="stop token id for --continuous (-1: disabled)")
    ap.add_argument("--paged", action="store_true",
                    help="block-paged KV cache + chunked prefill (with "
                         "--continuous); every registry arch pages — dense/"
                         "window K/V, MLA compressed latents, recurrent "
                         "carries")
    ap.add_argument("--block-size", type=int, default=16,
                    help="tokens per KV page for --paged")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="page pool size (default: full provisioning)")
    ap.add_argument("--kv-dtype", default="f32",
                    choices=["f32", "bf16", "int8", "fp8"],
                    help="paged-pool storage dtype; int8/fp8 (require "
                         "--paged) quantize pages with per-slot f32 scales "
                         "— greedy parity becomes a tolerance lane vs the "
                         "float mirror, not byte parity")
    ap.add_argument("--chunk-len", type=int, default=None,
                    help="max prefill chunk width per iteration for --paged")
    ap.add_argument("--no-overlap", action="store_true",
                    help="disable dispatch-then-fetch double buffering")
    ap.add_argument("--prefix-cache", action="store_true", default=False,
                    help="prefix-sharing radix cache over the page pool "
                         "(with --paged); synthetic requests then share a "
                         "common system prefix; window/recurrent archs "
                         "match via published carry snapshots")
    ap.add_argument("--no-prefix-cache", dest="prefix_cache",
                    action="store_false",
                    help="serve every prompt cold (default)")
    ap.add_argument("--spec-depth", type=int, default=None,
                    help="self-speculative decoding: draft = the served "
                         "model truncated to this many layers (with "
                         "--paged); recurrent archs roll back via "
                         "checkpoint rings")
    ap.add_argument("--gamma", type=int, default=4,
                    help="draft tokens proposed per speculation round")
    ap.add_argument("--draft-checkpoint", default=None,
                    help="draft from this checkpoint (restored at its "
                         "manifest depth) instead of depth truncation")
    ap.add_argument("--age-limit", type=float, default=None,
                    help="admission aging threshold in seconds (paged "
                         "first-fit blocks for the oldest request past it)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request deadline from arrival (finish reason "
                         "'deadline' past it — queued, prefilling, or "
                         "mid-decode; partial tokens are returned)")
    ap.add_argument("--queue-limit", type=int, default=None,
                    help="bound on the arrived-but-unadmitted queue; "
                         "overflow requests are shed with a structured "
                         "rejection instead of queueing unboundedly")
    ap.add_argument("--retries", type=int, default=2,
                    help="bounded retry-with-backoff for transient "
                         "admission/prefill/decode faults before failing "
                         "the one affected request")
    ap.add_argument("--faults", default=None, metavar="TAPE",
                    help="deterministic fault injection: 'site:nth[:kind]' "
                         "entries joined by commas (kind: fault|crash; "
                         "sites: " + ", ".join(faults_lib.SITES)
                         + ") or 'storm:rate[:seed]' for a seeded "
                         "Bernoulli fault storm")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="snapshot host-side in-flight serving state every "
                         "N iteration boundaries (crash recovery: restore "
                         "re-prefills prompt+emitted for byte-identical "
                         "resumed greedy streams; 0: off)")
    ap.add_argument("--invariant-every", type=int, default=0,
                    help="audit pool refcounts/commitments + radix pins "
                         "every N scheduler iterations (0: off)")
    args = ap.parse_args(argv)
    compile_cache.enable()
    if args.paged and not args.continuous:
        raise SystemExit("--paged requires --continuous")
    spec = args.spec_depth is not None or args.draft_checkpoint is not None
    if spec and not args.paged:
        raise SystemExit("--spec-depth/--draft-checkpoint require --paged")
    if args.prefix_cache and not args.paged:
        raise SystemExit("--prefix-cache requires --paged")
    if args.kv_dtype in ("int8", "fp8") and not args.paged:
        raise SystemExit("--kv-dtype int8/fp8 requires --paged (scales are "
                         "per-pool-page state)")

    cfg = (cfglib.get_smoke_config(args.arch) if args.smoke
           else cfglib.get_config(args.arch))
    mesh = mesh_lib.make_train_mesh(args.mesh)
    if args.checkpoint:
        params, cfg = load_params(args.checkpoint, cfg, step=args.step)
    else:
        api = registry.get_model(cfg)
        params = api.init(jax.random.PRNGKey(args.seed), cfg)
    draft_params = None
    if args.draft_checkpoint:          # its own latest step, manifest depth
        draft_params, _ = load_params(args.draft_checkpoint, cfg)
    rng = np.random.default_rng(args.seed)
    # With the prefix cache on, continuous requests share a system prefix
    # (half the prompt budget, but at least one full page — only full pages
    # publish into the radix tree) so the cache has traffic to hit.
    shared_len = (max(args.prompt_len // 2, args.block_size)
                  if args.prefix_cache else 0)
    engine = ServeEngine(cfg, params, mesh=mesh,
                         max_len=shared_len + args.prompt_len
                         + max(args.gen, 1) + 1,
                         paged=args.paged, block_size=args.block_size,
                         num_blocks=args.num_blocks,
                         spec_decode=spec, gamma=args.gamma,
                         draft_depth=args.spec_depth,
                         draft_params=draft_params,
                         prefix_cache=args.prefix_cache,
                         kv_dtype=args.kv_dtype, faults=args.faults)

    if args.continuous:
        shared = rng.integers(0, cfg.vocab_size,
                              (shared_len,)).astype(np.int32)
        lens = rng.integers(max(2, args.prompt_len // 4), args.prompt_len + 1,
                            args.requests)
        gens = rng.integers(max(2, args.gen // 4), max(args.gen, 2) + 1,
                            args.requests)
        arrivals = np.cumsum(rng.exponential(1.0 / args.rate, args.requests))
        reqs = [Request(prompt=np.concatenate(
                    [shared, rng.integers(0, cfg.vocab_size,
                                          (int(p),)).astype(np.int32)]),
                        max_new_tokens=int(g), arrival_s=float(a))
                for p, g, a in zip(lens, gens, arrivals)]
        sched = ContinuousScheduler(engine, max_batch=args.max_batch,
                                    temperature=args.temperature,
                                    eos_id=args.eos, seed=args.seed,
                                    chunk_len=args.chunk_len,
                                    overlap=not args.no_overlap,
                                    admission_age_s=args.age_limit,
                                    deadline_s=args.deadline_s,
                                    queue_limit=args.queue_limit,
                                    max_retries=args.retries,
                                    invariant_every=args.invariant_every,
                                    snapshot_every=args.snapshot_every)
        sched.warmup(reqs)             # compile outside the timed run
        t0 = time.perf_counter()
        results = sched.run(reqs, on_finish=lambda r: print(
            f"  req {r.uid}: +{len(r.new_tokens)} tok ({r.finish_reason}) "
            f"ttft={r.ttft_s * 1e3:.1f}ms"))
        stats = summarize(results, time.perf_counter() - t0)
        mode = "spec" if spec else ("paged" if args.paged else "continuous")
        print(f"arch={cfg.name} layers={cfg.num_layers} mesh={args.mesh} "
              f"{mode} max_batch={args.max_batch} "
              f"requests={args.requests} "
              f"peak_concurrency={sched.peak_concurrency}")
        print(f"aggregate tokens/s={stats['tokens_per_s']:.1f}  "
              f"ttft p50={stats['ttft_p50_s'] * 1e3:.1f}ms "
              f"p95={stats['ttft_p95_s'] * 1e3:.1f}ms")
        fs = sched.fault_stats()
        if stats["completed"] < stats["requests"] or fs["retries"] \
                or args.faults:
            reasons = " ".join(f"{k}={v}" for k, v in
                               sorted(stats["finish_reasons"].items()))
            print(f"lifecycle: {reasons} retries={fs['retries']} "
                  f"goodput tokens/s={stats['goodput']:.1f} "
                  f"(all: {stats['tokens_per_s_all']:.1f})")
        if args.paged:
            ks = sched.kv_stats()
            print(f"kv storage: dtype={ks['kv_dtype']} "
                  f"bytes/token={ks['kv_bytes_per_token']:.1f} "
                  f"(f32: {ks['kv_bytes_per_token_f32']:.1f}, "
                  f"ratio={ks['kv_bytes_ratio']:.3f})")
        if args.prefix_cache:
            ps = sched.prefix_stats()
            print(f"prefix cache: hits={ps['prefix_hits']}/"
                  f"{ps['prefix_requests']} "
                  f"(rate={ps['prefix_hit_rate']:.2%}) "
                  f"skipped_tokens={ps['prefix_skipped_tokens']}")
        if spec:
            ss = sched.spec_stats()
            mal = [r.mean_accepted_len for r in results if r.spec_rounds]
            print(f"speculative: draft_layers={engine.draft_cfg.num_layers} "
                  f"gamma={engine.gamma} rounds={ss['spec_rounds']} "
                  f"acceptance={ss['acceptance_rate']:.2%} "
                  f"mean_accepted_len="
                  f"{np.mean(mal) if mal else 0.0:.2f}")
        return results

    prompts = rng.integers(0, cfg.vocab_size,
                           (args.batch, args.prompt_len)).astype(np.int32)
    warmup = min(2, max(args.gen, 1))                           # compile
    engine.generate(prompts, warmup, temperature=args.temperature)
    res = engine.generate(prompts, max(args.gen, 1),
                          temperature=args.temperature, seed=args.seed)
    pf = args.batch * res.prefill_tokens / max(res.prefill_s, 1e-9)
    dec = args.batch * max(res.steps - 1, 0) / max(res.decode_s, 1e-9)
    print(f"arch={cfg.name} layers={cfg.num_layers} mesh={args.mesh} "
          f"batch={args.batch} decode_steps={res.steps}")
    print(f"prefill tokens/s={pf:.1f}  decode tokens/s={dec:.1f}")
    print("sample:", res.tokens[0, :24].tolist())
    return res


if __name__ == "__main__":
    main()
