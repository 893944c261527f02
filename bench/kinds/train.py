"""Training cells: ``ProgressiveTrainer.run`` over a seeded token file.

Set-up builds one trainer, with the benchmark's seeded weights, and its
``run`` drives the compiled step through the first ``CHECK_STEPS`` steps;
the recorder around the step keeps what the check needs from them and then
opens the window, and the same ``run`` call goes on through the window.
The window closes when ``run`` returns and its state is ready, so it holds
every step after the first ``CHECK_STEPS``, the depth expansion of a
progressive mix among them.  Nothing compiles there: the step count, and
so every traced constant, follows from ``--seconds`` alone, and a
progressive mix first runs a warm-up episode of the same schedule that
expands after two steps and stops after the first deep one.

``correct`` holds the run to ``reference/gpt2.py`` and to the mix:

* the first three steps, from the benchmark's seeded weights: each step's
  loss, the first gradient as the optimizer received it (its momentum
  after one step), and how far each weight moved over the three steps,
  the latter two per weight matrix (per layer) by the gap of their norms;
* the depth of every step, against the mix's schedule;
* at an expansion, the state the first deep step receives: every
  inherited weight, momentum and counter bit for bit, the new layers'
  momentum zero and their LayerNorms at 1 and 0 (exactly); the new
  layers' matrices against the declared init, normal with std
  1/sqrt(fan_in), as z-scores of their mean and standard deviation;
* the first deep step itself.  The recorder puts new layers that the
  benchmark draws from the seed in place of the program's, so the
  reference can start that step from a state it builds itself: the
  inherited part (held equal to the program's by the exact check) and
  its own draw.  Its gradient is compared as above.  Its loss gap is
  read too (``deep_loss_gap``) but a cell need not compare it: the
  bfloat16 control reads no higher there than sound runs do.
"""
from __future__ import annotations

import functools
import gc
import math
import os
import sys
import tempfile
import time

import harness
import jax
import jax.numpy as jnp
import numpy as np
from reference import gpt2 as ref
from traffic import corpus as corpus_gen

CHECK_STEPS = 3
REF_ROWS = 4            # rows per block of the reference's gradient
MOVED_FLOOR = 1e-3      # leaves whose reference gradient is under this
                        # share of the median leaf's take no part in the
                        # weight-change number: they move by rounding alone
DRAW_STREAM = 1         # folded into the seed's key for the new layers
NORM_INIT = {"scale": 1.0, "bias": 0.0}


class _WarmupDone(Exception):
    pass


class Feed:
    """The trainer's data source: ``BinCorpus`` with a span and a clock
    reading around every ``batch`` call."""

    def __init__(self, corpus, spans):
        self.corpus = corpus
        self.spans = spans
        self.fetches = []          # (step, host time at the call)

    def batch(self, step, shard=0, num_shards=1):
        self.fetches.append((int(step), time.perf_counter()))
        with self.spans.span("batch"):
            return self.corpus.batch(step, shard, num_shards)


def _names(path):
    return [str(getattr(p, "key", p)) for p in path]


def leaf_norms(tree) -> dict:
    """{leaf name: norm}; layer-stacked leaves give one norm per layer."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(_names(path))
        x = x.astype(jnp.float32)
        if name.startswith("blocks/"):
            n = jnp.sqrt(jnp.sum(jnp.square(x.reshape(x.shape[0], -1)), 1))
            for i in range(x.shape[0]):
                out[f"{name}#{i}"] = n[i]
        else:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(x)))
    return out


_leaf_norms = jax.jit(leaf_norms)
_change_norms = jax.jit(lambda a, b: leaf_norms(
    jax.tree.map(lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32),
                 a, b)))
_copy = jax.jit(lambda t: jax.tree.map(jnp.copy, t))


def _floats(norms) -> dict:
    return {k: float(v) for k, v in norms.items()}


# -- the expansion -----------------------------------------------------------

def _bits(x):
    return jax.lax.bitcast_convert_type(
        x, {2: jnp.uint16, 4: jnp.uint32, 8: jnp.uint64}[x.dtype.itemsize])


def _like(new, old):
    """``new`` cut to the keys ``old`` has."""
    if isinstance(old, dict):
        return {k: _like(new[k], v) for k, v in old.items()}
    return new


def exact_diff(old_p, old_s, new_p, new_s):
    """Elements of the expanded state that differ from what the expansion
    must give exactly: every inherited weight, momentum and optimizer
    counter bit for bit (a stacked leaf's first layers), the new layers'
    momentum zero, and their LayerNorms at scale 1 and bias 0."""
    src = ref.num_layers(old_p)

    def inherited(path, a, b):
        if "blocks" in _names(path):
            b = b[:src]
        return jnp.sum(_bits(a) != _bits(b))

    diff = sum(jax.tree.leaves([jax.tree_util.tree_map_with_path(
        inherited, old, _like(new, old))
        for old, new in ((old_p, new_p), (old_s, new_s))]))
    for x in jax.tree.leaves(new_s["m"]["blocks"]):
        diff = diff + jnp.sum(x[src:] != 0)
    for path, x in jax.tree_util.tree_flatten_with_path(new_p["blocks"])[0]:
        last = _names(path)[-1]
        if last in NORM_INIT:
            diff = diff + jnp.sum(x[src:] != NORM_INIT[last])
    return diff


def init_z(blocks):
    """The largest z-score, over the stacked layers and matrices of
    ``blocks``, of a matrix's sample mean and standard deviation under the
    declared init (normal, mean 0, std 1/sqrt(fan_in)): about standard
    normal each for a sound draw, whatever the widths."""
    zs = [jnp.zeros((), jnp.float32)]
    for path, x in jax.tree_util.tree_flatten_with_path(blocks)[0]:
        if _names(path)[-1] in NORM_INIT:
            continue
        sigma = 1.0 / math.sqrt(x.shape[-2])
        x = x.astype(jnp.float32).reshape(x.shape[0], -1)
        n = x.shape[1]
        mean = jnp.mean(x, 1)
        std = jnp.sqrt(jnp.mean(jnp.square(x - mean[:, None]), 1))
        zs.append(jnp.max(jnp.abs(mean) / sigma * math.sqrt(n)))
        zs.append(jnp.max(jnp.abs(std / sigma - 1.0) * math.sqrt(2 * n)))
    return jnp.max(jnp.stack(zs))


def _grow(old, new_layers, src):
    """``old`` with each stacked leaf followed by ``new_layers``' layers
    from ``src`` on."""
    if src == 0:
        return dict(old, blocks=new_layers)
    return dict(old, blocks=jax.tree.map(
        lambda a, d: jnp.concatenate([a, d[src:]]), old["blocks"],
        new_layers))


def draw_key(seed):
    return jax.random.fold_in(jax.random.PRNGKey(harness.key_seed(seed)),
                              DRAW_STREAM)


@functools.lru_cache(maxsize=None)
def _draw(model_items, layers):
    m = dict(model_items)
    return jax.jit(lambda key: ref.init(key, m, layers)["blocks"])


@functools.lru_cache(maxsize=None)
def _boundary(model_items):
    """The jitted look at the state a first deep step receives: (that
    state with the benchmark's new layers in place of the program's,
    the exact and init numbers, a copy of the pre-expansion weights and
    momentum for the reference)."""
    m = dict(model_items)

    def fn(old_p, old_s, new_p, new_s, key):
        src, tgt = ref.num_layers(old_p), ref.num_layers(new_p)
        draw = ref.init(key, m, tgt)["blocks"]
        swapped = dict(new_p, blocks=jax.tree.map(
            lambda a, d: jnp.concatenate([a[:src], d[src:]]),
            new_p["blocks"], draw))
        checks = {"expand_exact_diff": exact_diff(old_p, old_s, new_p, new_s),
                  "new_layer_init_z": init_z(jax.tree.map(
                      lambda x: x[src:], new_p["blocks"]))}
        snap = jax.tree.map(jnp.copy, (old_p, old_s["m"]))
        return swapped, checks, snap
    return jax.jit(fn)


def expansion_step(cell, steps):
    """The first step the mix runs at full depth, floor(tau * steps) and
    at least 1; None for a fixed-depth mix."""
    if cell.traffic["source_layers"] >= cell.model["num_layers"]:
        return None
    return max(1, int(cell.traffic["tau"] * steps))


def depth_errors(cell, steps, depths) -> int:
    """Steps run at another depth than the schedule's, and steps missing
    or extra."""
    at = expansion_step(cell, steps)
    want = [cell.model["num_layers"] if at is None or i >= at
            else cell.traffic["source_layers"] for i in range(steps)]
    return sum(a != b for a, b in zip(depths, want)) + abs(len(depths)
                                                           - steps)


# -- the recorder around the step --------------------------------------------

class Recorder:
    """Wraps the trainer's compiled step: spans every call and records the
    depth it ran at; keeps the first ``check_steps`` steps' losses, the
    first gradient's norms (from the momentum after one step, which is
    that gradient) and the weights' change over those steps, then calls
    ``on_check_done``; at the first deeper step, looks at the expanded
    state and swaps in the benchmark's new layers (``_boundary``), and
    keeps that step's loss and momentum.  ``after_step`` sees every
    step's depth and output."""

    def __init__(self, spans, initial_params, check_steps, on_check_done,
                 model, seed, after_step=None):
        self.spans = spans
        self.initial_params = initial_params
        self.check_steps = check_steps
        self.on_check_done = on_check_done
        self.model_items = tuple(sorted(model.items()))
        self.key = draw_key(seed)
        self.after_step = after_step
        self.calls = 0
        self.depths = []
        self.losses = []
        self.grad_norms = None
        self.change_norms = None
        self.held = None          # the latest shallow step's output state
        self.boundary = None

    def wrap(self, fn):
        def step(params, opt_state, batch, step_idx, *rest):
            layers = ref.num_layers(params)
            deep = bool(self.depths) and layers > self.depths[-1]
            if deep:
                params, checks, snap = _boundary(self.model_items)(
                    *self.held, params, opt_state, self.key)
                self.held = None
                self.boundary = dict(checks, snap=snap, step=self.calls,
                                     layers=layers)
            with self.spans.span("step"):
                out = fn(params, opt_state, batch, step_idx, *rest)
            i = self.calls
            self.calls += 1
            self.depths.append(layers)
            if deep:
                self.boundary.update(loss=out[2]["loss"],
                                     m_after=_copy(out[1]["m"]))
            elif self.boundary is None:
                self.held = out[:2]
            if i < self.check_steps:
                new_params, new_state, metrics = out
                self.losses.append(metrics["loss"])
                if i == 0:
                    self.grad_norms = _leaf_norms(new_state["m"])
                if i == self.check_steps - 1:
                    self.change_norms = _change_norms(
                        new_params, self.initial_params())
                    jax.block_until_ready((out, self.change_norms))
                    self.on_check_done(out)
            if self.after_step is not None:
                self.after_step(layers, out)
            return out
        return step

    def check_values(self):
        """(losses, first-gradient norms, change norms) of the first
        steps, on the host."""
        return ([float(x) for x in self.losses], _floats(self.grad_norms),
                _floats(self.change_norms))


# -- building the trainer ----------------------------------------------------

def program_cfg(cell):
    from repro.configs.base import ModelConfig
    return ModelConfig(**cell.model)


def _train_config(cell, total_steps, seed, at_frac=None):
    from repro.configs.base import (ExpansionConfig, OptimizerConfig,
                                    ScheduleConfig, TrainConfig)
    mix, m = cell.traffic, cell.model
    opt, sch = mix["optimizer"], mix["schedule"]
    src = mix["source_layers"]
    exps = ()
    if src < m["num_layers"]:
        exps = (ExpansionConfig(
            at_frac=mix["tau"] if at_frac is None else at_frac,
            target_layers=m["num_layers"], init=mix["init"],
            opt_state_policy=mix["os_policy"]),)
    never = 1 << 40
    return TrainConfig(
        total_steps=total_steps, seq_len=mix["seq_len"],
        global_batch=mix["batch"], source_layers=src, expansions=exps,
        optimizer=OptimizerConfig(
            name="muon_nsgd", learning_rate=opt["lr"],
            weight_decay=opt["weight_decay"], momentum=opt["momentum"],
            ns_steps=opt["ns_steps"]),
        schedule=ScheduleConfig(name="wsd", warmup_frac=sch["warmup_frac"],
                                decay_frac=sch["decay_frac"],
                                min_lr_frac=sch["min_lr_frac"]),
        seed=harness.key_seed(seed), remat=mix["remat"], log_every=never,
        eval_every=never, checkpoint_every=never)


def total_steps(cell, seconds: float) -> int:
    """Steps of the run: the check's, then enough for ``seconds`` at the
    cell's nominal step time (a whole-number function of ``--seconds``, so
    every run of a cell compiles the same programs)."""
    return CHECK_STEPS + max(1, math.ceil(seconds / cell.workload["step_s"]))


def weights_fn(cell, seed, layers, shardings=None):
    m = cell.model
    make = jax.jit(lambda k: ref.init(k, m, layers), out_shardings=shardings)
    return lambda: make(jax.random.PRNGKey(harness.key_seed(seed)))


class Corpus:
    """The seeded token file of a run, in a temporary directory."""

    def __init__(self, cell, seed):
        self.dir = tempfile.TemporaryDirectory(prefix="bench-corpus-")
        self.path = os.path.join(self.dir.name, "tokens.bin")
        corpus_gen.write(self.path, seed, cell.model["vocab_size"],
                         cell.traffic)

    def reader(self, cell, seed):
        from repro.data.corpus import BinCorpus
        mix = cell.traffic
        return BinCorpus(self.path, cell.model["vocab_size"], mix["seq_len"],
                         mix["batch"], seed=seed)

    def close(self):
        self.dir.cleanup()


def build_trainer(cell, seed, steps, corpus, spans, on_check_done,
                  at_frac=None, check_steps=CHECK_STEPS, after_step=None):
    from repro.launch import mesh as mesh_lib
    from repro.train.engine import ProgressiveTrainer
    tcfg = _train_config(cell, steps, seed, at_frac)
    feed = Feed(corpus.reader(cell, seed), spans)
    trainer = ProgressiveTrainer(
        program_cfg(cell), tcfg, mesh=mesh_lib.make_train_mesh(
            cell.traffic["mesh"]), data=feed, eval_batches=[],
        async_ckpt=False, log_fn=lambda *a: None)
    src = tcfg.source_layers
    make = {}

    def init_state(cfg, p_sh, os_sh):
        make["fn"] = weights_fn(cell, seed, src, p_sh)
        params = make["fn"]()
        if jax.tree.structure(params) != jax.tree.structure(p_sh):
            raise ValueError("the benchmark's weights do not match the "
                             "program's parameter tree")
        return params, jax.jit(trainer.opt.init, out_shardings=os_sh)(params)

    rec = Recorder(spans, lambda: make["fn"](), check_steps, on_check_done,
                   cell.model, seed, after_step)
    build = trainer._build_steps

    def build_steps(cfg, p_sh, os_sh):
        with spans.span("build_steps"):
            step, ev = build(cfg, p_sh, os_sh)
        return rec.wrap(step), ev

    trainer._init_state = init_state
    trainer._build_steps = build_steps
    return trainer, feed, rec


def warm_expansion(cell, seed, steps, corpus):
    """Compile the shallow step, the expansion, the look at it and the
    deep step of this schedule: the same trainer, expanded after two
    steps and stopped once the first deep step is done."""
    deep = cell.model["num_layers"]

    def stop_when_deep(layers, out):
        if layers == deep:
            jax.block_until_ready(out)
            raise _WarmupDone

    trainer, _, _ = build_trainer(cell, seed, steps, corpus, harness.Spans(),
                                  None, at_frac=2.0 / steps, check_steps=0,
                                  after_step=stop_when_deep)
    try:
        trainer.run()
    except _WarmupDone:
        pass


# -- the check ---------------------------------------------------------------

def _precision(dtype):
    return jax.default_matmul_precision(
        "highest" if dtype == jnp.float32 else "default")


def reference_steps(cell, seed, steps, corpus, dtype=jnp.float32,
                    rows_used=None):
    """The first ``CHECK_STEPS`` steps of the reference from the same
    weights and rows: (losses, first-gradient norms, change norms).
    ``dtype`` below float32, or ``rows_used`` under the batch, put the
    low-precision control or the half-batch fault in the program's place."""
    m, mix = cell.model, cell.traffic
    eps = cell.config["layer_norm_epsilon"]
    data = corpus.reader(cell, seed)
    p0 = weights_fn(cell, seed, mix["source_layers"])()
    params = jax.tree.map(lambda x: x.astype(dtype), p0)
    mom = jax.tree.map(jnp.zeros_like, params)
    losses, gnorms = [], None
    opt = mix["optimizer"]
    muon = jax.jit(lambda p, mom, g, lr: ref.muon_nsgd(p, mom, g, lr, opt))
    with _precision(dtype):
        for i in range(CHECK_STEPS):
            b = data.batch(i)
            toks, labels = b["tokens"], b["labels"]
            if rows_used is not None:
                toks, labels = toks[:rows_used], labels[:rows_used]
            lr = ref.wsd_lr(i, steps, mix["schedule"], mix["optimizer"]["lr"])
            lv, g = ref.loss_and_grads(params, m, jnp.asarray(toks),
                                       jnp.asarray(labels), eps, REF_ROWS)
            losses.append(float(lv))
            if i == 0:
                gnorms = _floats(_leaf_norms(g))
            params, mom = muon(params, mom, g, jnp.asarray(lr, dtype))
        change = _floats(_change_norms(params, p0))
    return losses, gnorms, change


def _new_layers(cell, seed, boundary):
    """(pre-expansion weights, momentum, their layer count, the
    benchmark's draw of the full-depth layers)."""
    old_p, old_m = boundary["snap"]
    draw = _draw(tuple(sorted(cell.model.items())),
                 boundary["layers"])(draw_key(seed))
    return old_p, old_m, ref.num_layers(old_p), draw


def reference_deep(cell, seed, corpus, boundary, dtype=jnp.float32,
                   rows_used=None):
    """The reference's first deep step, from the pre-expansion weights
    grown by the benchmark's draw: (loss, gradient norms).  ``dtype`` and
    ``rows_used`` as for ``reference_steps``."""
    old_p, _, src, draw = _new_layers(cell, seed, boundary)
    params = jax.tree.map(lambda x: x.astype(dtype), _grow(old_p, draw, src))
    b = corpus.reader(cell, seed).batch(boundary["step"])
    toks, labels = b["tokens"], b["labels"]
    if rows_used is not None:
        toks, labels = toks[:rows_used], labels[:rows_used]
    with _precision(dtype):
        lv, g = ref.loss_and_grads(params, cell.model, jnp.asarray(toks),
                                   jnp.asarray(labels),
                                   cell.config["layer_norm_epsilon"],
                                   REF_ROWS)
        return float(lv), _floats(_leaf_norms(g))


def program_deep(cell, seed, boundary):
    """The program's first deep step: (loss, gradient norms), the gradient
    being its momentum after the step less beta times the momentum it
    received (the inherited one, new layers at zero)."""
    _, old_m, src, draw = _new_layers(cell, seed, boundary)
    mom = _grow(old_m, jax.tree.map(jnp.zeros_like, draw), src)
    beta = cell.traffic["optimizer"]["momentum"]
    g = jax.tree.map(lambda a, b: a - beta * b, boundary["m_after"], mom)
    return float(boundary["loss"]), _floats(_leaf_norms(g))


def _worst_gap(prog: dict, want: dict, keys=None) -> float:
    """The worst leaf's |prog - want| over max(want, the median leaf's)."""
    med = float(np.median(list(want.values())))
    return max(abs(prog[k] - want[k]) / max(want[k], med)
               for k in (want if keys is None else keys))


def compare(prog, want) -> dict:
    """The numbers of the first steps, from (losses, first-gradient
    norms, change norms) of the program and of the reference."""
    (lp, gp, dp), (lr_, gr, dr) = prog, want
    med_g = float(np.median(list(gr.values())))
    moved = [k for k in dr if gr.get(k, med_g) >= MOVED_FLOOR * med_g]
    return {"loss_gap": max(abs(a - b) for a, b in zip(lp, lr_)),
            "grad_gap": _worst_gap(gp, gr),
            "change_gap": _worst_gap(dp, {k: dr[k] for k in moved})}


def compare_deep(prog, want) -> dict:
    """The numbers of the first deep step, from (loss, gradient norms)."""
    (lp, gp), (lr_, gr) = prog, want
    return {"deep_loss_gap": abs(lp - lr_), "deep_grad_gap": _worst_gap(gp,
                                                                       gr)}


def boundary_values(cell, seed, corpus, boundary) -> dict:
    return {"expand_exact_diff": int(boundary["expand_exact_diff"]),
            "new_layer_init_z": float(boundary["new_layer_init_z"]),
            **compare_deep(program_deep(cell, seed, boundary),
                           reference_deep(cell, seed, corpus, boundary))}


# -- one run -----------------------------------------------------------------

def host_lead(stats, window) -> str:
    """Where the host's batch fetches fell in the window (seconds from its
    start): the first, the first deep step's, the last, and the wait from
    the last to the window's close, which is how far the host ran ahead of
    the device."""
    t = [f - window.t0 for f in stats["fetches"]]
    if not t:
        return ""
    d = stats["depths"]
    deep = next((i for i in range(1, len(d)) if d[i] > d[i - 1]), None)
    at = f" deep_fetch {t[deep]:.3f}" if deep is not None else ""
    return (f"fetch_first {t[0]:.3f}{at} fetch_last {t[-1]:.3f} "
            f"drain {window.seconds - t[-1]:.3f}")


def run(cell, args, devices, window, spans) -> dict:
    steps = total_steps(cell, args.seconds)
    corpus = Corpus(cell, args.seed)
    try:
        if expansion_step(cell, steps) is not None:
            warm_expansion(cell, args.seed, steps, corpus)
        trainer, feed, rec = build_trainer(
            cell, args.seed, steps, corpus, spans,
            lambda out: window.open())
        res = trainer.run()
        jax.block_until_ready((res.params, res.opt_state))
        window.close()
        final_loss = res.history["loss"][-1]
        peak = harness.memory_peak(devices)
        prog, boundary = rec.check_values(), rec.boundary
        stats = {"steps": steps, "window_steps": steps - CHECK_STEPS,
                 "depths": rec.depths[CHECK_STEPS:],
                 "fetches": [t for s, t in feed.fetches
                             if s >= CHECK_STEPS],
                 "batch_s": spans.durations.get("batch", [])[CHECK_STEPS:],
                 "expansion_steps": res.history["expansion_steps"]}
        errors = depth_errors(cell, steps, rec.depths)
        print(f"train: steps {steps} window_s {window.seconds:.3f} "
              f"expansions {stats['expansion_steps']} final_loss "
              f"{final_loss:.4f} {host_lead(stats, window)}",
              file=sys.stderr)
        del res, trainer, rec, feed
        gc.collect()
        values = compare(prog, reference_steps(cell, args.seed, steps,
                                               corpus))
        values["depth_errors"] = errors
        if boundary is not None:
            values.update(boundary_values(cell, args.seed, corpus, boundary))
    finally:
        corpus.close()
    mix = cell.traffic
    tokens = stats["window_steps"] * mix["batch"] * mix["seq_len"]
    return {"e2e": {"train_tokens_per_s": tokens / window.seconds},
            "values": values, "final_loss": final_loss,
            "attempted": steps, "failed": 0 if math.isfinite(final_loss)
            else 1, "memory_peak_bytes": peak, "stats": stats}


def readings(cell, seed, seconds, modes) -> dict:
    """The numbers of one seed without a window: the program's
    (``program``: its first steps, and for a progressive mix its
    expansion and first deep step), and those of the reference put in its
    place in bfloat16 (``bf16``) or on half of each batch
    (``half_batch``); for a progressive mix also ``copy_init``, the init
    number of new layers copied from the last inherited one."""
    steps = total_steps(cell, seconds)
    grows = expansion_step(cell, steps) is not None
    deep = cell.model["num_layers"]
    corpus = Corpus(cell, seed)
    out = {}
    try:
        want = reference_steps(cell, seed, steps, corpus)
        if grows:
            warm_expansion(cell, seed, steps, corpus)

        def stop_checked(out):
            if not grows:
                raise _WarmupDone

        def stop_deep(layers, out):
            if grows and layers == deep:
                jax.block_until_ready(out)
                raise _WarmupDone
        trainer, _, rec = build_trainer(cell, seed, steps, corpus,
                                        harness.Spans(), stop_checked,
                                        after_step=stop_deep)
        try:
            trainer.run()
        except _WarmupDone:
            pass
        prog, boundary = rec.check_values(), rec.boundary
        del trainer, rec
        gc.collect()
        values = compare(prog, want)
        if grows:
            values.update(boundary_values(cell, seed, corpus, boundary))
            want_deep = reference_deep(cell, seed, corpus, boundary)
        if "program" in modes:
            out["program"] = values
        faults = {"bf16": {"dtype": jnp.bfloat16},
                  "half_batch": {"rows_used": cell.traffic["batch"] // 2}}
        for mode, kw in faults.items():
            if mode in modes:
                v = compare(reference_steps(cell, seed, steps, corpus, **kw),
                            want)
                if grows:
                    v.update(compare_deep(reference_deep(
                        cell, seed, corpus, boundary, **kw), want_deep))
                out[mode] = v
        if grows and "copy_init" in modes:
            old_p, _, src, _ = _new_layers(cell, seed, boundary)
            copied = jax.tree.map(lambda a: jnp.repeat(a[-1:], deep - src, 0),
                                  old_p["blocks"])
            out["copy_init"] = {"new_layer_init_z": float(
                jax.jit(init_z)(copied))}
    finally:
        corpus.close()
    return out
