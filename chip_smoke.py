#!/usr/bin/env python3
"""Chip smoke test: the paper's main path on a TPU, end to end.

    python chip_smoke.py              # one chip: kernels, train, resume, serve
    python chip_smoke.py --four-chip  # four chips: 2x2 sharded training
                                      # against the same steps on one chip

One process drives everything: a chip belongs to one process at a time, so
nothing here starts a subprocess.  One-chip phases, at the published widths
of ``gpt2-12l`` (the paper's Figure 1 model; weights random from a seed):

  kernels  each Pallas kernel of the GPT-2 path on the chip against its jnp
           reference computed at ``precision=HIGHEST``: flash attention
           forward and gradient, Newton–Schulz for every Muon matrix shape,
           paged decode over f32 and int8 pools;
  train    ``launch/train.py`` from one layer, expanded to 12 at tau=0.5,
           Muon-NSGD, seq 1024, batch 8, 8 steps, activations recomputed
           in the backward pass (``--remat nothing``: without it the
           12-layer step needs about 15 GiB of temporaries), checkpointing
           into ``.smoke_ckpt/``;
  resume   the same command for 10 steps: restores step 8, trains 2 more;
  serve    ``launch/serve.py --continuous --paged`` on that checkpoint:
           8 requests, prompts up to 128, up to 32 new tokens, max batch 4.
           Greedy-token agreement with a teacher-forced forward pass is
           reported, not gated (the byte-parity contracts are CPU ones).

Every phase runs even after another fails; any failure exits 1.  The last
line of stdout is one JSON object naming the device, printed only when
every phase passed.  Without a TPU, or without the repository's ``src/``
next to this file, it exits nonzero and says why.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import math
import os
import shutil
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
CKPT_DIR = ROOT / ".smoke_ckpt"

ARCH = "gpt2-12l"
SEQ, BATCH, STEPS, RESUME_STEPS = 1024, 8, 8, 10
REQUESTS, MAX_BATCH, PROMPT, GEN = 8, 4, 128, 32
KERNEL_BATCH = 8

# Kernel-vs-reference bound, as max |kernel - ref| / max |ref|.  On the
# chip, matmuls of f32 operands may multiply in bf16 (8-bit mantissa,
# relative rounding 2^-9 per operand); over the 64-wide head and 1024-long
# sequence sums of these kernels that is a few 1e-3 (a v5e chip gave
# 0.0028 to 0.0052).  A masking, indexing or page-table error moves the
# output by O(1), far above this bound.
ATTN_BOUND = 2e-2
# Newton–Schulz runs five quintic iterations whose polynomial has slope
# up to 3.4 near zero, so input rounding can grow by up to that factor
# per iteration before the singular values saturate; a v5e chip gave
# 0.023 (fused 768x768) to 0.041 (tiled 3072x768).  A dropped or
# misplaced tile gives O(1).
NS_BOUND = 1e-1


class _Tee(io.TextIOBase):
    """Writes to stdout and keeps a copy, so a phase can check what the
    CLIs printed."""

    def __init__(self, out):
        self.out, self.buf = out, io.StringIO()

    def write(self, s):
        self.out.write(s)
        self.buf.write(s)
        return len(s)

    def flush(self):
        self.out.flush()


def _run_cli(main, argv):
    """Run a CLI ``main`` in this process; returns (result, its stdout)."""
    tee = _Tee(sys.stdout)
    with contextlib.redirect_stdout(tee):
        res = main(argv)
    return res, tee.buf.getvalue()


def _rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _check(name: str, err: float, bound: float):
    ok = math.isfinite(err) and err <= bound
    print(f"[kernel] {name}: max rel err {err!r} (bound {bound}) "
          f"{'ok' if ok else 'FAIL'}", flush=True)
    if not ok:
        raise AssertionError(f"{name}: error {err!r} exceeds {bound}")


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def phase_kernels():
    """Each repaired kernel on the chip against its jnp reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import quant
    from repro.kernels.flash_attention import ops as fa_ops
    from repro.kernels.flash_attention import ref as fa_ref
    from repro.kernels.newton_schulz import ops as ns_ops
    from repro.kernels.newton_schulz.ref import newton_schulz_ref
    from repro.kernels.paged_attention.kernel import paged_attention_tpu
    from repro.kernels.paged_attention import ref as pa_ref

    cfg = _config()
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    highest = jax.default_matmul_precision("highest")
    ks = jax.random.split(jax.random.PRNGKey(0), 8)

    # flash attention: forward, and dq/dk/dv through the custom VJP
    shape = (KERNEL_BATCH, SEQ, H, hd)
    q, k, v, do = (jax.random.normal(ks[i], shape, jnp.float32)
                   for i in range(4))
    fwd = jax.jit(fa_ops.flash_attention)
    grad = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(fa_ops.flash_attention(q, k, v) * do),
        argnums=(0, 1, 2)))
    with highest:
        want = jax.jit(fa_ref.naive_attention)(q, k, v)
        want_g = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(fa_ref.naive_attention(q, k, v) * do),
            argnums=(0, 1, 2)))(q, k, v)
    _check("flash_attention forward", _rel_err(fwd(q, k, v), want),
           ATTN_BOUND)
    for name, got, ref in zip(("dq", "dk", "dv"), grad(q, k, v), want_g):
        _check(f"flash_attention {name}", _rel_err(got, ref), ATTN_BOUND)

    # Newton–Schulz at every Muon matrix shape of the model
    d, ff, V = cfg.d_model, cfg.d_ff, cfg.vocab_size
    for n_in, n_out in ((d, d), (d, ff), (ff, d), (V, d)):
        m = jax.random.normal(ks[4], (n_in, n_out), jnp.float32)
        got = ns_ops.newton_schulz(m)
        with highest:
            want = jax.jit(newton_schulz_ref)(m)
        path = "fused" if ns_ops.fits_fused(min(n_in, n_out),
                                            max(n_in, n_out)) else "tiled"
        _check(f"newton_schulz {n_in}x{n_out} ({path})",
               _rel_err(got, want), NS_BOUND)

    # paged decode over f32 and int8 pools, permuted pages, ragged cursors
    rows, bs = 4, 16
    nb = SEQ // bs
    NP = rows * nb + 1
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.permutation(NP)[:rows * nb].reshape(rows, nb),
                        jnp.int32)
    index = jnp.asarray(rng.integers(0, SEQ, rows), jnp.int32)
    qd = jax.random.normal(ks[5], (rows, 1, H, hd), jnp.float32)
    kp = jax.random.normal(ks[6], (NP, bs, KV, hd), jnp.float32)
    vp = jax.random.normal(ks[7], (NP, bs, KV, hd), jnp.float32)
    kq, kscale = quant.quantize(kp, axis=-1, dtype=jnp.int8)
    vq, vscale = quant.quantize(vp, axis=-1, dtype=jnp.int8)
    for name, pool, scales in (("f32", (kp, vp), {}),
                               ("int8", (kq, vq), {"k_scales": kscale,
                                                   "v_scales": vscale})):
        got = jax.jit(paged_attention_tpu)(qd, *pool, table, index, **scales)
        with highest:
            want = jax.jit(pa_ref.paged_attention_ref)(qd, *pool, table,
                                                        index, **scales)
        _check(f"paged_attention decode ({name} pool, block 16)",
               _rel_err(got, want), ATTN_BOUND)


def _config():
    from repro import configs as cfglib
    return cfglib.get_config(ARCH)


def _train_argv(steps, mesh="single", ckpt=None):
    argv = ["--arch", ARCH, "--mesh", mesh,
            "--source-layers", "1", "--tau", "0.5", "--seq-len", str(SEQ),
            "--batch", str(BATCH), "--steps", str(steps),
            "--remat", "nothing"]
    return argv + (["--ckpt-dir", str(ckpt)] if ckpt else [])


def phase_train():
    from repro.launch import train as train_cli
    layers = _config().num_layers
    shutil.rmtree(CKPT_DIR, ignore_errors=True)
    res, out = _run_cli(train_cli.main, _train_argv(STEPS, ckpt=CKPT_DIR))
    expand = f"[expand] step={STEPS // 2} -> {layers} layers"
    if expand not in out:
        raise AssertionError(f"no {expand!r} line")
    loss = res.history["loss"][-1]
    if not math.isfinite(loss) or res.final_layers != layers:
        raise AssertionError(f"final loss {loss}, layers {res.final_layers}")
    print(f"[train] ok: final loss {loss!r} at {res.final_layers} layers",
          flush=True)


def phase_resume():
    from repro.launch import train as train_cli
    res, out = _run_cli(train_cli.main,
                        _train_argv(RESUME_STEPS, ckpt=CKPT_DIR))
    line = f"[resume] step={STEPS} "
    if line not in out:
        raise AssertionError(f"no {line!r} line")
    loss = res.history["loss"][-1]
    if not math.isfinite(loss) or res.history["step"][-1] != RESUME_STEPS - 1:
        raise AssertionError(f"resumed run ended at step "
                             f"{res.history['step'][-1]} with loss {loss}")
    print(f"[resume] ok: final loss {loss!r}", flush=True)


def phase_serve():
    import jax
    import numpy as np
    from repro.launch import serve as serve_cli
    from repro.models import registry
    results, _ = _run_cli(serve_cli.main, [
        "--arch", ARCH, "--checkpoint", str(CKPT_DIR),
        "--continuous", "--paged", "--requests", str(REQUESTS),
        "--max-batch", str(MAX_BATCH), "--prompt-len", str(PROMPT),
        "--gen", str(GEN)])
    reasons = [r.finish_reason for r in results]
    if len(results) != REQUESTS or any(x not in ("eos", "limit")
                                       for x in reasons):
        raise AssertionError(f"finish reasons {reasons}")

    # Greedy agreement with one teacher-forced forward over each finished
    # stream: generated token i should be the argmax at position P-1+i.
    params, cfg = serve_cli.load_params(str(CKPT_DIR), _config())
    L = max(len(r.tokens) for r in results)
    toks = np.zeros((len(results), L), np.int32)
    for i, r in enumerate(results):
        toks[i, :len(r.tokens)] = r.tokens
    api = registry.get_model(cfg)
    logits = jax.jit(lambda p, t: api.apply(p, cfg, {"tokens": t}))(
        params, toks)
    pred = np.asarray(jax.numpy.argmax(logits, -1))
    hit = tot = 0
    for i, r in enumerate(results):
        P, g = len(r.prompt), len(r.new_tokens)
        hit += int((pred[i, P - 1:P - 1 + g] == r.new_tokens).sum())
        tot += g
    print(f"[serve] ok: {len(results)} requests finished {sorted(set(reasons))}; "
          f"greedy agreement with a teacher-forced forward {hit}/{tot} "
          f"(reported, not gated)", flush=True)


def phase_four_chip():
    """2x2-sharded training against the same steps on one chip."""
    import jax
    from repro.launch import train as train_cli
    results = {}
    for mesh in ("2x2", "single"):
        res, _ = _run_cli(train_cli.main, _train_argv(STEPS, mesh=mesh))
        stats = [d.memory_stats() or {} for d in jax.devices()]
        print(f"[four-chip] mesh={mesh} losses={res.history['loss']!r} "
              f"bytes_in_use per device="
              f"{[m.get('bytes_in_use') for m in stats]} peak="
              f"{[m.get('peak_bytes_in_use') for m in stats]}", flush=True)
        results[mesh] = res
    a, b = results["2x2"].history["loss"], results["single"].history["loss"]
    diff = max(abs(x - y) for x, y in zip(a, b))
    print(f"[four-chip] largest loss difference 2x2 vs one chip: {diff!r} "
          f"over steps {results['2x2'].history['step']}", flush=True)
    if not all(math.isfinite(x) for x in a + b) or diff > 5e-2:
        raise AssertionError(f"loss difference {diff!r}")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the 2x2-sharded training on four chips "
                         "and its one-chip comparison")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no src/repro next to this script in {ROOT}; run "
              f"it from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no libtpu logs in /tmp
    from repro.launch import compile_cache
    compile_cache.enable()          # before the first compile
    import jax
    devices = jax.devices()
    dev = devices[0]
    want = 4 if args.four_chip else 1
    if dev.platform != "tpu" or len(devices) < want:
        print(f"chip_smoke: needs {want} TPU chip(s); JAX found "
              f"{len(devices)} {dev.platform} device(s)", file=sys.stderr)
        return 1
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}",
          flush=True)

    phases = ([phase_four_chip] if args.four_chip else
              [phase_kernels, phase_train, phase_resume, phase_serve])
    failed = []
    for phase in phases:
        name = phase.__name__[len("phase_"):]
        t0 = time.perf_counter()
        try:
            phase()
        except Exception:
            traceback.print_exc()
            failed.append(name)
        print(f"[phase] {name}: {'FAIL' if name in failed else 'ok'} "
              f"({time.perf_counter() - t0:.1f} s wall, compile included)",
              flush=True)
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
