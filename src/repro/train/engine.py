"""Mesh-aware progressive training engine.

The ``ProgressiveTrainer`` runs the paper's recipe (§7) — source-model
training → depth expansion at τ → grown-model training under one schedule
and one optimizer — entirely *under a mesh*.  The sharding/microbatching
contract:

  * At init, per-leaf ``NamedSharding``s for params and optimizer state are
    resolved from ``repro.distributed.sharding`` (MaxText-style name+shape
    rules: TP over 'model', FSDP over 'data', pure DP over 'pod') against
    the engine's mesh.  Train/eval steps are compiled with explicit
    ``in_shardings``/``out_shardings`` and donated params+opt-state, so
    state lives in its mesh layout for the whole run — there is no implicit
    host round-trip anywhere in the hot path.
  * Batches are host-generated at ``global_batch`` and placed sharded over
    the data axes (``batch_shardings``).  With ``tcfg.grad_accum = A`` the
    step scans A microbatches of ``global_batch/A`` with gradient
    averaging, so the global batch size is decoupled from the device count:
    the same config trains identically on 1 chip or 512 (up to float
    reassociation).
  * Depth expansion runs jitted under the mesh (``expansion.make_expand_fn``):
    expanded block stacks come back with their per-leaf shardings at the new
    depth and the train step is re-jitted against them — an on-device
    reshape/concat, never a host transfer.
  * Checkpoints gather to host (elastic: restore re-shards onto whatever
    mesh the restoring run uses, including a different device count), and
    every expansion boundary is checkpointed.  With ``async_ckpt=True``
    (default) the gather + file write overlap the next train step via
    ``checkpoint.AsyncCheckpointer`` (device-side snapshot first — the
    train step donates the originals; only the manifest is fsync'd).

``repro.train.loop.train`` wraps this engine with a degenerate 1x1 mesh,
keeping the historical single-device API (and bit-exact numerics) intact.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import checkpointer as ckpt
from repro.configs.base import ModelConfig, TrainConfig
from repro.core import expansion as exp
from repro.core.schedules import make_schedule
from repro.data.synthetic import DataConfig, SyntheticLM, make_eval_batches
from repro.distributed import sharding as shd
from repro.distributed.collectives import StragglerMonitor
from repro.launch import mesh as mesh_lib
from repro.models import common as model_common
from repro.models import registry
from repro.optim.base import make_optimizer
from repro.spans import span
from repro.train import faults as faults_lib
from repro.train import steps as steps_lib


def _bytes_on(device, tree) -> int:
    """Bytes of ``tree``'s arrays that lie on ``device``."""
    return sum(s.data.nbytes for x in jax.tree.leaves(tree)
               for s in x.addressable_shards if s.device == device)


@dataclasses.dataclass
class TrainResult:
    history: Dict[str, List]
    params: object
    opt_state: object
    final_layers: int
    # Robustness telemetry: retry/containment counters plus the fault
    # plane's coverage receipts (empty dicts on a clean, unfaulted run).
    fault_stats: Dict = dataclasses.field(default_factory=dict)


class ProgressiveTrainer:
    """Sharded progressive-training engine (see module docstring)."""

    def __init__(self, model_cfg: ModelConfig, tcfg: TrainConfig,
                 mesh=None, checkpoint_dir: Optional[str] = None,
                 data: Optional[SyntheticLM] = None, eval_batches=None,
                 dtype=jnp.float32, log_fn: Callable = print,
                 fsdp: bool = True, layout: str = "tp",
                 moe_fsdp: str = "auto", async_ckpt: bool = True,
                 faults=None, nan_policy: str = "off",
                 spike_factor: float = 10.0, nan_inject=None,
                 expansion_guard: bool = False, guard_window: int = 20,
                 guard_tol: float = 1.5, guard_defer: Optional[int] = None,
                 guard_max_retries: int = 2, nan_rollback_after: int = 3,
                 max_retries: int = 2, retry_backoff_s: float = 0.05,
                 hang_deadline_s: Optional[float] = None):
        """Robustness knobs (all off by default — the clean path is
        byte-identical to the un-instrumented engine):

        ``faults``            fault plane / spec string (``faults.resolve``);
                              train sites fire before every fragile op and
                              transient faults are retried ``max_retries``
                              times with ``retry_backoff_s`` exponential
                              backoff (``CrashError`` always unwinds; failed
                              checkpoint writes are contained and counted).
        ``nan_policy``        'off' | 'warn' | 'skip' | 'rollback' — the
                              sentinel ladder for bad steps (non-finite
                              loss/grad-norm, or grad-norm >
                              ``spike_factor`` x its EMA).  'skip' discards
                              the update on device; 'rollback' additionally
                              restores the latest checkpoint after
                              ``nan_rollback_after`` consecutive bad steps
                              (once per run — injected faults are
                              deterministic, so replaying forever would
                              loop), then degrades to skip.
        ``nan_inject``        'kind:step[@attempt],...' numerical-fault
                              injections baked into the step (tests).
        ``expansion_guard``   arm the post-expansion divergence watchdog:
                              for ``guard_window`` steps after τ the loss
                              EMA is compared against the pre-expansion
                              baseline; past ``guard_tol`` x baseline (or a
                              non-finite loss) the boundary checkpoint is
                              restored and the expansion retried with
                              ``copying_zeroL`` init, then deferred by
                              ``guard_defer`` steps, at most
                              ``guard_max_retries`` times.
        ``hang_deadline_s``   StragglerMonitor hard ceiling: a slower step
                              raises a ``train.step`` fault (recorded in
                              ``history['hangs']``) instead of stalling.
        """
        if tcfg.global_batch % max(tcfg.grad_accum, 1):
            raise ValueError(f"global_batch {tcfg.global_batch} not divisible "
                             f"by grad_accum {tcfg.grad_accum}")
        if nan_policy not in ("off", "warn", "skip", "rollback"):
            raise ValueError(f"unknown nan_policy {nan_policy!r}")
        # Param init and 'random' expansion run inside jit under
        # out_shardings, so random bits must not depend on the layout they
        # are generated in: the legacy threefry lowering bakes the device
        # layout into the bits (sharded init != single-device init), the
        # partitionable lowering does not (and is the default on newer jax).
        # Scoped to engine construction — importing this module changes
        # nothing — and an explicit JAX_THREEFRY_PARTITIONABLE setting wins.
        if "JAX_THREEFRY_PARTITIONABLE" not in os.environ:
            jax.config.update("jax_threefry_partitionable", True)
        self.model_cfg = model_cfg
        self.tcfg = tcfg
        self.mesh = mesh if mesh is not None else mesh_lib.single_device_mesh()
        self.checkpoint_dir = checkpoint_dir
        self.dtype = dtype
        self.log_fn = log_fn
        self.fsdp = fsdp
        self.layout = layout
        self.moe_fsdp = moe_fsdp
        # Async checkpointing (ROADMAP): the device->host gather and file
        # write overlap the next train step (the checkpointer snapshots on
        # device first — params/opt-state are donated into that step).
        self._ckptr = ckpt.AsyncCheckpointer() if async_ckpt else None

        self.faults = faults_lib.resolve(faults)
        self.nan_policy = nan_policy
        self.spike_factor = spike_factor
        self.nan_inject = faults_lib.parse_nan_inject(nan_inject)
        self.expansion_guard = expansion_guard
        self.guard_window = guard_window
        self.guard_tol = guard_tol
        self.guard_defer = guard_defer if guard_defer is not None \
            else guard_window
        self.guard_max_retries = guard_max_retries
        self.nan_rollback_after = nan_rollback_after
        self.max_retries = max_retries
        self.retry_backoff_s = retry_backoff_s
        self.hang_deadline_s = hang_deadline_s
        # Sentinel metrics ride the step only when something consumes them.
        self._sentinels = (nan_policy != "off" or bool(self.nan_inject)
                           or expansion_guard)
        self._guard_attempt = 0       # scopes @attempt nan-injections
        self.retries = 0
        self.ckpt_failures = 0
        self.nan_rollbacks = 0

        dcfg = DataConfig(vocab_size=model_cfg.vocab_size,
                          seq_len=tcfg.seq_len,
                          global_batch=tcfg.global_batch, seed=tcfg.seed)
        self.data = data or SyntheticLM(dcfg)
        self.eval_batches = (eval_batches if eval_batches is not None
                             else make_eval_batches(dcfg, tcfg.eval_batches))

        self.opt = make_optimizer(tcfg.optimizer)
        self.schedule = make_schedule(tcfg.schedule,
                                      tcfg.optimizer.learning_rate,
                                      tcfg.total_steps)
        # batch shardings: data-axis on dim 0, resolved once against the
        # DataConfig shapes (depth-independent; no host batch is generated
        # just to learn them).  grad_accum microbatches re-resolve the spec
        # at their own batch size (steps._microbatch).
        sample = {k: jax.ShapeDtypeStruct(
                      (tcfg.global_batch, tcfg.seq_len), np.int32)
                  for k in ("tokens", "labels")}
        self._batch_sh = shd.batch_shardings(sample, self.mesh,
                                             layout=self.layout)
        self._replicated = shd.replicated(self.mesh)

    # -- sharding resolution -------------------------------------------------

    def _state_shardings(self, cfg: ModelConfig):
        """Per-leaf (shardings, abstract structs) for params/opt-state at
        cfg's depth.  Nothing is allocated — structs come from eval_shape."""
        api = registry.get_model(cfg)
        p_struct = jax.eval_shape(
            lambda k: api.init(k, cfg, dtype=self.dtype),
            jax.random.PRNGKey(0))
        os_struct = jax.eval_shape(self.opt.init, p_struct)
        p_sh = shd.params_shardings(p_struct, self.mesh, fsdp=self.fsdp,
                                    moe_fsdp=self.moe_fsdp, layout=self.layout)
        os_sh = shd.opt_state_shardings(os_struct, self.mesh, fsdp=self.fsdp,
                                        moe_fsdp=self.moe_fsdp,
                                        layout=self.layout)
        return p_sh, os_sh, p_struct, os_struct

    def _step_shardings(self, p_sh, os_sh) -> steps_lib.StepShardings:
        return steps_lib.StepShardings(mesh=self.mesh, params=p_sh,
                                       opt_state=os_sh, batch=self._batch_sh,
                                       replicated=self._replicated,
                                       layout=self.layout)

    def _build_steps(self, cfg: ModelConfig, p_sh, os_sh):
        sh = self._step_shardings(p_sh, os_sh)
        train_step = steps_lib.make_train_step(
            cfg, self.opt, self.schedule, remat=self.tcfg.remat,
            grad_accum=self.tcfg.grad_accum, shardings=sh,
            sentinels=self._sentinels,
            nan_policy=self.nan_policy if self.nan_policy != "off" else "warn",
            spike_factor=self.spike_factor,
            inject=faults_lib.active_inject(self.nan_inject,
                                           self._guard_attempt))
        eval_step = steps_lib.make_eval_step(cfg, shardings=sh)
        return train_step, eval_step

    def _retry(self, site: str, fn):
        """Run ``fn`` containing transient ``FaultError``s with bounded
        exponential backoff.  ``CrashError`` is never caught (it models
        process death); exhaustion re-raises the last fault."""
        attempt = 0
        while True:
            try:
                return fn()
            except faults_lib.CrashError:
                raise
            except faults_lib.FaultError as e:
                if attempt >= self.max_retries:
                    raise
                attempt += 1
                self.retries += 1
                self.log_fn(f"[fault] {site}: {e} — retry "
                            f"{attempt}/{self.max_retries}")
                if self.retry_backoff_s:
                    time.sleep(self.retry_backoff_s * (2 ** (attempt - 1)))

    def _restore_state(self, step: int):
        """Load checkpoint label ``step`` (= steps completed) and return
        (metadata, layers, cfg, p_sh, os_sh, params, opt_state); restore
        only needs abstract structs, so no throwaway init is materialized
        and the leaves re-shard elastically onto this run's mesh."""
        meta = ckpt.load_metadata(self.checkpoint_dir, step)
        cur_layers = int(meta["num_layers"])
        cur_cfg = self.model_cfg.with_depth(cur_layers)
        p_sh, os_sh, p_struct, os_struct = self._state_shardings(cur_cfg)
        restored = self._retry("ckpt.restore", lambda: ckpt.restore(
            self.checkpoint_dir, step,
            {"params": p_struct, "opt_state": os_struct},
            shardings={"params": p_sh, "opt_state": os_sh},
            faults=self.faults))
        return (meta, cur_layers, cur_cfg, p_sh, os_sh,
                restored["params"], restored["opt_state"])

    def _init_state(self, cfg: ModelConfig, p_sh, os_sh):
        """Initialize params/opt-state directly into their mesh layout."""
        api = registry.get_model(cfg)
        params = jax.jit(lambda k: api.init(k, cfg, dtype=self.dtype),
                         out_shardings=p_sh)(
            jax.random.PRNGKey(self.tcfg.seed))
        opt_state = jax.jit(self.opt.init, out_shardings=os_sh)(params)
        return params, opt_state

    def _place_batch(self, host_batch):
        return jax.device_put(dict(host_batch), self._batch_sh)

    # -- main loop -----------------------------------------------------------

    def run(self) -> TrainResult:
        # Activation constraints (model_common.maybe_shard) must agree with
        # the engine's param/batch rules: register both the mesh and the
        # activation layout for the duration of the run.
        prev_mesh = model_common.get_active_mesh()
        prev_layout = model_common.get_activation_layout()
        model_common.set_active_mesh(self.mesh)
        model_common.set_activation_layout(self.layout)
        try:
            return self._run()
        finally:
            model_common.set_active_mesh(prev_mesh)
            model_common.set_activation_layout(prev_layout)

    def _run(self) -> TrainResult:
        tcfg, model_cfg = self.tcfg, self.model_cfg
        plane = self.faults
        exp_steps = {max(1, int(e.at_frac * tcfg.total_steps)): e
                     for e in sorted(tcfg.expansions, key=lambda e: e.at_frac)}

        history = {"step": [], "loss": [], "lr": [], "eval_step": [],
                   "eval_loss": [], "layers": [], "expansion_steps": [],
                   "step_time": [], "sentinel": [], "skipped_steps": [],
                   "expansion_guard": [], "hangs": []}
        # Host-side sentinel/guard state.  The EMAs ride checkpoint metadata
        # so a resumed run's spike/divergence tests see the same baselines.
        gnorm_ema = 0.0
        loss_ema = None
        bad_streak = 0
        guard = {"boundary": -1, "until": -1, "baseline": None,
                 "attempt": 0, "retries": 0}
        guard_events: List[dict] = []

        # ----- resume or fresh init ----------------------------------------
        # Checkpoint labels mean "steps completed", so start_step = label
        # replays nothing: the periodic save for step k runs AFTER its
        # update under label k+1, and the expansion-boundary save(τ) (made
        # BEFORE the expansion mutates params) already counts τ completed
        # steps.  Before this convention the two save paths disagreed and a
        # resume re-ran the checkpointed step (one batch trained twice).
        start_step = 0
        cur_layers = tcfg.source_layers
        meta = None
        if self.checkpoint_dir:
            latest = ckpt.latest_step(self.checkpoint_dir)
            if latest is not None:
                (meta, cur_layers, cur_cfg, p_sh, os_sh,
                 params, opt_state) = self._restore_state(latest)
                start_step = latest
                for k, v in meta.get("history", {}).items():
                    history[k] = list(v)
                guard_events = list(history["expansion_guard"])
                gnorm_ema = float(meta.get("gnorm_ema", 0.0))
                loss_ema = meta.get("loss_ema")
                g = meta.get("guard")
                if g:
                    guard.update(g)
                    self._guard_attempt = int(guard["attempt"])
                self.log_fn(f"[resume] step={start_step} layers={cur_layers}")
        if meta is None:
            cur_cfg = model_cfg.with_depth(cur_layers)
            p_sh, os_sh, _, _ = self._state_shardings(cur_cfg)
            with span("train.init") as init:
                params, opt_state = self._init_state(cur_cfg, p_sh, os_sh)
                jax.block_until_ready((params, opt_state))
                init.note(state_bytes=_bytes_on(self.mesh.devices.flat[0],
                                                (params, opt_state)))

        train_step, eval_step = self._build_steps(cur_cfg, p_sh, os_sh)
        monitor = StragglerMonitor(hang_deadline_s=self.hang_deadline_s)

        def save(done):
            """Checkpoint with label = completed steps (see resume note)."""
            if not self.checkpoint_dir:
                return
            m = {"num_layers": cur_layers, "name": model_cfg.name,
                 # The data cursor IS the step index (SyntheticLM.batch is
                 # step-keyed), recorded explicitly for external consumers.
                 "data_step": done,
                 "gnorm_ema": gnorm_ema, "loss_ema": loss_ema,
                 "guard": dict(guard),
                 "history": {k: v for k, v in history.items()
                             if k != "step_time"}}
            # Deep-copy now: the async writer serializes in the background
            # while this loop keeps appending to history.  step_time is
            # excluded above — wall-clock noise has no business making two
            # otherwise-identical checkpoints differ.
            m = json.loads(json.dumps(m))
            saver = self._ckptr.save if self._ckptr else ckpt.save

            def write():
                saver(self.checkpoint_dir, done,
                      {"params": params, "opt_state": opt_state},
                      metadata=m, keep=tcfg.keep_checkpoints, faults=plane)

            try:
                self._retry("ckpt.write", write)
            except faults_lib.FaultError as e:
                # A lost checkpoint degrades recovery granularity but must
                # not kill the run — training continues from device state.
                self.ckpt_failures += 1
                self.log_fn(f"[ckpt] save({done}) failed after retries: {e}")

        def reload(at, why):
            """Roll device state back to checkpoint label ``at`` (resume
            semantics: history/EMAs come back from its metadata; events
            recorded since — the guard log — are re-applied on top)."""
            nonlocal params, opt_state, cur_layers, cur_cfg, p_sh, os_sh
            nonlocal train_step, eval_step, gnorm_ema, loss_ema
            if self._ckptr is not None:
                try:
                    self._ckptr.wait()      # don't race an in-flight write
                except faults_lib.FaultError:
                    self.ckpt_failures += 1
            (m, cur_layers, cur_cfg, p_sh, os_sh,
             params, opt_state) = self._restore_state(at)
            for k, v in m.get("history", {}).items():
                history[k] = list(v)
            history["expansion_guard"] = list(guard_events)
            gnorm_ema = float(m.get("gnorm_ema", 0.0))
            loss_ema = m.get("loss_ema")
            train_step, eval_step = self._build_steps(cur_cfg, p_sh, os_sh)
            self.log_fn(f"[rollback] {why}: restored checkpoint {at} "
                        f"({cur_layers} layers)")

        step = start_step
        while step < tcfg.total_steps:
            plane.fire("train.iter")        # scheduled-crash point

            # ---- depth expansion at τ (paper's technique) ------------------
            if step in exp_steps and cur_layers < exp_steps[step].target_layers:
                e = exp_steps[step]

                def expand():
                    plane.fire("train.expand")
                    expand_fn, new_p_sh, new_os_sh = exp.make_expand_fn(
                        cur_cfg, e.target_layers, e.init, params, opt_state,
                        insert_at=e.insert_at,
                        opt_state_policy=e.opt_state_policy, dtype=self.dtype,
                        mesh=self.mesh, fsdp=self.fsdp, layout=self.layout,
                        moe_fsdp=self.moe_fsdp)
                    key = jax.random.PRNGKey(tcfg.seed + 17 + step)
                    return expand_fn(params, opt_state, key), \
                        new_p_sh, new_os_sh

                with span("train.expand"):
                    save(step)               # expansion boundary checkpoint
                    (params, opt_state), p_sh, os_sh = \
                        self._retry("train.expand", expand)
                    cur_layers = e.target_layers
                    cur_cfg = model_cfg.with_depth(cur_layers)
                    train_step, eval_step = self._build_steps(cur_cfg, p_sh,
                                                              os_sh)
                history["expansion_steps"].append(step)
                self.log_fn(f"[expand] step={step} -> {cur_layers} layers "
                            f"({e.init}, OS={e.opt_state_policy})")
                if self.expansion_guard:
                    guard.update(boundary=step,
                                 until=step + self.guard_window,
                                 baseline=loss_ema)

            def fetch_batch():
                plane.fire("train.batch")
                return self._place_batch(self.data.batch(step))

            with span("train.fetch"):
                batch = self._retry("train.batch", fetch_batch)

            def dispatch():
                plane.fire("train.step")
                if self._sentinels:
                    return train_step(params, opt_state, batch,
                                      jnp.asarray(step),
                                      jnp.float32(gnorm_ema))
                return train_step(params, opt_state, batch, jnp.asarray(step))

            with span("train.dispatch") as dispatched:
                params, opt_state, metrics = self._retry("train.step",
                                                         dispatch)
            try:
                dt, slow = monitor.observe(dispatched.seconds)
            except faults_lib.FaultError as e:
                # The hung step HAS run (buffers donated): record, move on.
                history["hangs"].append(step)
                dt, slow = monitor.last_dt, True
                self.log_fn(f"[hang] step {step}: {e}")

            # ---- numerical sentinels (device-computed, host-policied) ------
            if self._sentinels:
                # One fused fetch: the first host sync blocks on the step
                # anyway, but three separate float() calls pay three
                # dispatch round-trips per step.
                loss_v, gnorm_ema, bad_v = map(float, jax.device_get(
                    (metrics["loss"], metrics["gnorm_ema"], metrics["bad"])))
                if not bad_v:
                    bad_streak = 0
                    loss_ema = loss_v if loss_ema is None \
                        else 0.8 * loss_ema + 0.2 * loss_v
                else:
                    bad_streak += 1
                    policy = self.nan_policy if self.nan_policy != "off" \
                        else "warn"
                    history["sentinel"].append(
                        {"step": step, "policy": policy, "loss": loss_v,
                         "grad_norm": float(metrics["grad_norm"])})
                    if policy in ("skip", "rollback"):
                        history["skipped_steps"].append(step)
                    self.log_fn(
                        f"[sentinel] step {step} bad (loss {loss_v:.4g}, "
                        f"|g| {float(metrics['grad_norm']):.4g}) -> {policy}")
                    if (policy == "rollback" and self.checkpoint_dir
                            and bad_streak >= self.nan_rollback_after
                            and self.nan_rollbacks < 1):
                        at = ckpt.latest_step(self.checkpoint_dir)
                        if at is not None and at <= step:
                            # Once per run: injections are deterministic, a
                            # replay hits them again — after one rollback the
                            # policy degrades to device-side skip.
                            self.nan_rollbacks += 1
                            reload(at, f"{bad_streak} consecutive bad steps")
                            bad_streak = 0
                            step = at
                            continue

            # ---- expansion guard: post-τ divergence watchdog ---------------
            if self.expansion_guard and guard["boundary"] >= 0:
                base = guard["baseline"]
                diverged = (not math.isfinite(loss_v)) or (
                    base is not None and loss_ema is not None
                    and loss_ema > self.guard_tol * max(base, 1e-8))
                if step < guard["until"] and diverged \
                        and self.checkpoint_dir:
                    btau = guard["boundary"]
                    guard["retries"] += 1
                    if guard["retries"] > self.guard_max_retries:
                        event = {"step": step, "boundary": btau,
                                 "attempt": guard["attempt"],
                                 "action": "give_up"}
                        guard_events.append(event)
                        history["expansion_guard"] = list(guard_events)
                        guard.update(boundary=-1, until=-1)
                        self.log_fn(f"[guard] give up after "
                                    f"{self.guard_max_retries} retries")
                    else:
                        e0 = exp_steps[btau]
                        if e0.init != "copying_zeroL":
                            # Function-preserving retry first: zero'd new
                            # blocks keep the pre-expansion function exactly.
                            exp_steps[btau] = dataclasses.replace(
                                e0, init="copying_zeroL")
                            action = "retry_zeroL"
                        else:
                            ntau = min(btau + self.guard_defer,
                                       tcfg.total_steps - 1)
                            exp_steps[ntau] = e0
                            del exp_steps[btau]
                            action = f"defer_to_{ntau}"
                        guard["attempt"] += 1
                        self._guard_attempt = guard["attempt"]
                        event = {"step": step, "boundary": btau,
                                 "attempt": guard["attempt"],
                                 "action": action,
                                 "loss_ema": loss_ema, "baseline": base}
                        guard_events.append(event)
                        reload(btau, "post-expansion divergence "
                                     f"(loss {loss_v:.4g}, loss_ema "
                                     f"{loss_ema} vs baseline {base})")
                        history["expansion_guard"] = list(guard_events)
                        guard.update(boundary=-1, until=-1, baseline=None)
                        self.log_fn(f"[guard] {action} at boundary {btau}")
                        bad_streak = 0
                        step = btau
                        continue
                elif step + 1 >= guard["until"]:
                    guard_events.append({"step": step,
                                         "boundary": guard["boundary"],
                                         "attempt": guard["attempt"],
                                         "action": "pass"})
                    history["expansion_guard"] = list(guard_events)
                    guard.update(boundary=-1, until=-1, baseline=None)
                    self.log_fn(f"[guard] probation passed at step {step}")

            if step % tcfg.log_every == 0 or step == tcfg.total_steps - 1:
                loss = float(metrics["loss"])
                history["step"].append(step)
                history["loss"].append(loss)
                history["lr"].append(float(metrics["lr"]))
                history["layers"].append(cur_layers)
                history["step_time"].append(dt)
                if step % (tcfg.log_every * 10) == 0:
                    self.log_fn(f"step {step:6d} layers {cur_layers:3d} "
                                f"loss {loss:.4f} "
                                f"lr {float(metrics['lr']):.2e}"
                                + ("  [straggler]" if slow else ""))

            if step and step % tcfg.eval_every == 0:
                def evaluate():
                    plane.fire("train.eval")
                    return float(np.mean(
                        [float(eval_step(params, self._place_batch(b)))
                         for b in self.eval_batches]))

                history["eval_step"].append(step)
                history["eval_loss"].append(self._retry("train.eval",
                                                        evaluate))

            done = step + 1
            if (self.checkpoint_dir and done % tcfg.checkpoint_every == 0
                    and done < tcfg.total_steps):
                save(done)
            step += 1

        save(tcfg.total_steps)
        if self._ckptr is not None:     # drain (and surface) in-flight write
            try:
                self._ckptr.wait()
            except faults_lib.FaultError as e:
                self.ckpt_failures += 1
                self.log_fn(f"[ckpt] final save failed: {e}")
        stats = {"retries": self.retries,
                 "ckpt_failures": self.ckpt_failures,
                 "nan_rollbacks": self.nan_rollbacks,
                 "skipped_steps": len(history["skipped_steps"]),
                 "hangs": len(history["hangs"]),
                 "guard_events": len(history["expansion_guard"]),
                 "fault_counts": dict(getattr(plane, "counts", {}) or {}),
                 "fired": list(getattr(plane, "fired", []))}
        return TrainResult(history=history, params=params,
                           opt_state=opt_state, final_layers=cur_layers,
                           fault_stats=stats)
