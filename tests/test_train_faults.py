"""Fault injection + recovery for the training engine (PR 9's serving suite,
mirrored onto ``ProgressiveTrainer``).

Contracts under test:

  * transient faults at every train-side site (batch/step/eval/expand and
    the checkpointer) are retried and leave the run BYTE-identical to an
    unfaulted one — sites fire before state mutates, so a retry replays
    nothing and corrupts nothing;
  * ``CrashError`` unwinds the loop; a restarted trainer resumes from the
    last complete checkpoint to byte-identical final params and loss
    history, for crashes sweeping an expansion-straddling window, landing
    mid-expansion (``train.expand``), and mid-async-checkpoint — which is
    only true because checkpoint labels mean "steps completed" (the
    resume-parity sweep is the regression test for the old off-by-one,
    where the periodic save's step was re-run on resume);
  * numerical sentinels: an injected NaN under policy 'skip' discards the
    update on device — params AND optimizer state — so the subsequent
    trajectory is identical to a run that never produced that batch's
    update; 'warn' demonstrably poisons; 'rollback' restores the latest
    checkpoint once and then degrades to skip;
  * the expansion guard rolls back a diverging post-expansion run to the
    boundary checkpoint exactly once per mitigation (function-preserving
    retry, then deferred τ) and the run completes;
  * a ``CrashError`` between the async checkpointer's device snapshot and
    the manifest fsync leaves ``latest_step`` at the previous complete
    checkpoint.
"""
import math
import os

import jax
import numpy as np
import pytest

from repro.checkpoint import checkpointer as ckpt
from repro.configs.base import (ExpansionConfig, ModelConfig, OptimizerConfig,
                                ScheduleConfig, TrainConfig)
from repro.distributed.collectives import StragglerMonitor
from repro.train.engine import ProgressiveTrainer
from repro.train.faults import (ITER_SITES, SITES, CrashError, FaultError,
                                FaultPlane, HangError, active_inject,
                                parse_nan_inject)

CFG = ModelConfig(name="tfault", family="dense", num_layers=2, d_model=32,
                  num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=64,
                  max_seq_len=16)

TAU = 6          # expansion lands at 0.5 * 12


def tcfg(**kw):
    base = dict(total_steps=12, seq_len=16, global_batch=4, source_layers=1,
                expansions=(ExpansionConfig(at_frac=0.5, target_layers=2,
                                            init="copying_zeroL"),),
                optimizer=OptimizerConfig(name="adamw", learning_rate=1e-3),
                schedule=ScheduleConfig(name="constant"),
                eval_every=10_000, eval_batches=1, seed=0, log_every=1,
                checkpoint_every=3, keep_checkpoints=100)
    base.update(kw)
    return TrainConfig(**base)


def run(tc=None, ckpt_dir=None, **kw):
    return ProgressiveTrainer(CFG, tc if tc is not None else tcfg(),
                              checkpoint_dir=ckpt_dir,
                              log_fn=lambda *a: None, **kw)


def leaves_equal(a, b) -> bool:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y), equal_nan=True)
        for x, y in zip(la, lb))


# ---------------------------------------------------------------------------
# FaultPlane units (train-side extensions)
# ---------------------------------------------------------------------------


def test_train_sites_registered():
    for s in ("train.batch", "train.step", "train.eval", "train.expand",
              "train.iter", "ckpt.restore"):
        assert s in SITES
    assert ITER_SITES == {"sched.iter", "train.iter"}


def test_parse_train_crash_spec():
    plane = FaultPlane.parse("train.iter:3:crash,train.step:1")
    with pytest.raises(FaultError):
        plane.fire("train.step")
    plane.fire("train.iter")
    plane.fire("train.iter")
    with pytest.raises(CrashError):
        plane.fire("train.iter")


def test_storm_never_hits_iteration_sites():
    plane = FaultPlane.seeded(1.0, seed=0)
    for _ in range(50):
        plane.fire("train.iter")        # rate 1.0 would fault every hit
        plane.fire("sched.iter")
    assert plane.counts["train.iter"] == 50 and not plane.fired


def test_parse_nan_inject_grammar():
    assert parse_nan_inject(None) == ()
    assert parse_nan_inject("nan:5") == (("nan", 5, None),)
    assert parse_nan_inject("spike:7@0,nan:9@2") == \
        (("spike", 7, 0), ("nan", 9, 2))
    assert active_inject("spike:7@0,nan:9@2,nan:3", 0) == \
        {7: "spike", 3: "nan"}
    assert active_inject("spike:7@0,nan:9@2,nan:3", 2) == \
        {9: "nan", 3: "nan"}
    with pytest.raises(ValueError):
        parse_nan_inject("explode:5")
    with pytest.raises(ValueError):
        parse_nan_inject("nan")


# ---------------------------------------------------------------------------
# Transient-fault containment: retried faults are byte-exact no-ops
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def clean_result():
    return run().run()


@pytest.mark.parametrize("site", ["train.batch", "train.step", "train.eval",
                                  "train.expand"])
def test_transient_fault_retried_to_byte_parity(site, clean_result):
    tc = tcfg(eval_every=4) if site == "train.eval" else tcfg()
    base = run(tc).run() if site == "train.eval" else clean_result
    plane = FaultPlane.parse(f"{site}:1,{site}:2")
    res = run(tc, faults=plane, max_retries=3, retry_backoff_s=1e-4).run()
    assert plane.counts[site] >= 3, "site never exercised (vacuous test)"
    assert len(plane.fired) == 2
    assert res.fault_stats["retries"] >= 2
    assert res.history["loss"] == base.history["loss"]
    assert leaves_equal(res.params, base.params)


def test_ckpt_write_fault_is_contained(tmp_path, clean_result):
    """A checkpoint write that fails even after retries must not kill the
    run — and must not perturb training state."""
    res = run(ckpt_dir=str(tmp_path), async_ckpt=False,
              faults="ckpt.write:1,ckpt.write:2,ckpt.write:3",
              max_retries=1, retry_backoff_s=1e-4).run()
    assert res.fault_stats["ckpt_failures"] >= 1
    assert leaves_equal(res.params, clean_result.params)
    # later saves succeeded: the run is still resumable
    assert ckpt.latest_step(str(tmp_path)) == 12


def test_retry_exhaustion_raises():
    spec = ",".join(f"train.step:{i}" for i in range(1, 6))
    with pytest.raises(FaultError):
        run(faults=spec, max_retries=0).run()


def test_fault_storm_with_retries_reaches_byte_parity(clean_result):
    plane = FaultPlane.seeded(0.05, seed=7)
    res = run(faults=plane, max_retries=5, retry_backoff_s=1e-4).run()
    assert plane.fired, "storm never fired (vacuous test)"
    assert res.history["loss"] == clean_result.history["loss"]
    assert leaves_equal(res.params, clean_result.params)


# ---------------------------------------------------------------------------
# Byte-identical preempt-resume (the off-by-one regression sweep)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k", [4, 5, 6, 7, 8])
def test_crash_resume_byte_parity_expansion_window(tmp_path, clean_result, k):
    """Crash the k-th loop iteration (k straddles τ=6 and the periodic
    checkpoints at 3/6/9) and resume: final params AND the loss curve must
    be byte-identical to the uninterrupted run.  Fails under the old save
    convention (periodic save labeled with the step it ran AFTER, so the
    resume re-ran that step: one batch trained twice)."""
    d = str(tmp_path)
    crashed = run(ckpt_dir=d, faults=f"train.iter:{k + 1}:crash")
    with pytest.raises(CrashError):
        crashed.run()
    # The crash unwinds the loop, not the process: the async checkpoint
    # writer it orphaned may still be writing step 3.  Join it before the
    # directory is read, or the resume races a half-written step — on a
    # loaded machine k=4, one step after that save, lost the race.
    crashed._ckptr.wait()
    assert ckpt.latest_step(d) is not None and ckpt.latest_step(d) <= k
    res = run(ckpt_dir=d).run()
    assert res.final_layers == 2
    assert res.history["step"] == clean_result.history["step"]
    assert res.history["loss"] == clean_result.history["loss"]
    assert res.history["expansion_steps"] == [TAU]
    assert leaves_equal(res.params, clean_result.params)


def test_crash_mid_expansion_resumes_to_parity(tmp_path, clean_result):
    """train.expand fires after the boundary checkpoint and before params
    mutate — the crash window inside the expansion itself."""
    d = str(tmp_path)
    with pytest.raises(CrashError):
        # sync checkpointing: the boundary write must have completed by the
        # time the crash unwinds, making the latest-step assert exact
        run(ckpt_dir=d, faults="train.expand:1:crash",
            async_ckpt=False).run()
    assert ckpt.latest_step(d) == TAU          # boundary ckpt completed
    assert ckpt.load_metadata(d, TAU)["num_layers"] == 1
    res = run(ckpt_dir=d).run()
    assert res.history["loss"] == clean_result.history["loss"]
    assert leaves_equal(res.params, clean_result.params)


def test_crash_mid_async_checkpoint_resumes_to_parity(tmp_path, clean_result):
    """A crash inside the async writer (between arrays and manifest)
    surfaces on the next wait and unwinds the run; the torn directory is
    invisible to resume."""
    d = str(tmp_path)
    with pytest.raises(CrashError):
        run(ckpt_dir=d, faults="ckpt.write:2:crash").run()
    res = run(ckpt_dir=d).run()
    assert res.history["loss"] == clean_result.history["loss"]
    assert leaves_equal(res.params, clean_result.params)


def test_ckpt_restore_fault_retried_on_resume(tmp_path, clean_result):
    d = str(tmp_path)
    with pytest.raises(CrashError):
        run(ckpt_dir=d, faults="train.iter:8:crash").run()
    plane = FaultPlane.parse("ckpt.restore:1")
    res = run(ckpt_dir=d, faults=plane, retry_backoff_s=1e-4).run()
    assert plane.counts["ckpt.restore"] >= 2      # fault + successful retry
    assert res.history["loss"] == clean_result.history["loss"]
    assert leaves_equal(res.params, clean_result.params)


def test_checkpoint_label_means_steps_completed(tmp_path):
    """Direct regression for the step-accounting bug: the checkpoint with
    label k must hold exactly the params of a run trained for k steps."""
    tc = tcfg(source_layers=2, expansions=(), checkpoint_every=5,
              total_steps=10)
    d = str(tmp_path)
    run(tc, ckpt_dir=d, async_ckpt=False).run()
    assert ckpt.all_steps(d) == [5, 10]
    short = run(tcfg(source_layers=2, expansions=(), total_steps=5)).run()
    a5 = dict(np.load(os.path.join(d, "step_000000005", "arrays.npz")))
    flat = [np.asarray(x) for x in jax.tree.leaves(
        {"params": short.params, "opt_state": short.opt_state})]
    assert len(flat) == len(a5)
    assert all(np.array_equal(a5[f"leaf_{i}"], x)
               for i, x in enumerate(flat))


# ---------------------------------------------------------------------------
# Numerical sentinels (NaN / spike policy ladder)
# ---------------------------------------------------------------------------


def _sentinel_tcfg(**kw):
    base = dict(source_layers=2, expansions=(), total_steps=10,
                checkpoint_every=1)
    base.update(kw)
    return tcfg(**base)


def test_nan_skip_discards_exactly_that_update(tmp_path):
    """checkpoint_every=1 turns adjacent checkpoints into the proof: the
    skipped step's before/after states are bitwise equal (params AND opt
    state — the update never happened), and healthy steps still move."""
    d = str(tmp_path)
    res = run(_sentinel_tcfg(), ckpt_dir=d, async_ckpt=False,
              nan_policy="skip", nan_inject="nan:5").run()
    assert res.history["skipped_steps"] == [5]
    assert math.isnan(res.history["loss"][5])
    assert all(math.isfinite(l) for i, l in enumerate(res.history["loss"])
               if i != 5)
    a5 = dict(np.load(os.path.join(d, "step_000000005", "arrays.npz")))
    a6 = dict(np.load(os.path.join(d, "step_000000006", "arrays.npz")))
    a7 = dict(np.load(os.path.join(d, "step_000000007", "arrays.npz")))
    assert all(np.array_equal(a5[k], a6[k], equal_nan=True) for k in a5)
    assert not all(np.array_equal(a6[k], a7[k], equal_nan=True) for k in a6)


def test_nan_warn_poisons_the_run():
    res = run(_sentinel_tcfg(checkpoint_every=100), nan_policy="warn",
              nan_inject="nan:5").run()
    assert [e["step"] for e in res.history["sentinel"]] and \
        res.history["sentinel"][0]["step"] == 5
    assert res.history["skipped_steps"] == []
    assert not math.isfinite(res.history["loss"][-1])


def test_spike_sentinel_detects_exploding_grads():
    res = run(_sentinel_tcfg(checkpoint_every=100), nan_policy="skip",
              nan_inject="spike:6", spike_factor=10.0).run()
    assert res.history["skipped_steps"] == [6]
    assert all(math.isfinite(l) for l in res.history["loss"])


def test_nan_rollback_restores_then_degrades_to_skip(tmp_path):
    """Policy 'rollback' restores the latest checkpoint after the streak;
    the deterministic injection refires on replay, so it then degrades to
    skip — ending byte-identical to the pure-skip run."""
    d1, d2 = str(tmp_path / "a"), str(tmp_path / "b")
    skip = run(_sentinel_tcfg(), ckpt_dir=d1, async_ckpt=False,
               nan_policy="skip", nan_inject="nan:5").run()
    rb = run(_sentinel_tcfg(), ckpt_dir=d2, async_ckpt=False,
             nan_policy="rollback", nan_inject="nan:5",
             nan_rollback_after=1).run()
    assert rb.fault_stats["nan_rollbacks"] == 1
    assert np.array_equal(rb.history["loss"], skip.history["loss"],
                          equal_nan=True)
    assert leaves_equal(rb.params, skip.params)


def test_clean_run_sentinels_silent(clean_result):
    """Sentinels on a healthy run: no events, same losses as the
    un-instrumented engine (the sentinel step adds metrics, not math)."""
    res = run(nan_policy="skip").run()
    assert res.history["sentinel"] == [] and \
        res.history["skipped_steps"] == []
    np.testing.assert_allclose(res.history["loss"],
                               clean_result.history["loss"], rtol=1e-6)


# ---------------------------------------------------------------------------
# Expansion guard (post-τ divergence watchdog)
# ---------------------------------------------------------------------------


def _guard_tcfg(init, **kw):
    base = dict(total_steps=16, checkpoint_every=100,
                expansions=(ExpansionConfig(at_frac=0.5, target_layers=2,
                                            init=init),))
    base.update(kw)
    return tcfg(**base)          # τ = 8


def test_expansion_guard_rolls_back_once_and_retries_zeroL(tmp_path):
    """Injected post-expansion divergence (attempt 0 only) triggers exactly
    one rollback to the boundary checkpoint; the retry switches to the
    function-preserving init and the run completes."""
    res = run(_guard_tcfg("random"), ckpt_dir=str(tmp_path),
              nan_policy="warn", nan_inject="spike:9@0,nan:10@0",
              expansion_guard=True, guard_window=6).run()
    acts = [e["action"] for e in res.history["expansion_guard"]]
    assert acts == ["retry_zeroL", "pass"]
    assert res.final_layers == 2
    assert res.history["expansion_steps"] == [8]
    assert math.isfinite(res.history["loss"][-1])


def test_expansion_guard_defers_tau_when_init_already_preserving(tmp_path):
    res = run(_guard_tcfg("copying_zeroL"), ckpt_dir=str(tmp_path),
              nan_policy="warn", nan_inject="nan:9@0",
              expansion_guard=True, guard_window=4, guard_defer=3).run()
    acts = [e["action"] for e in res.history["expansion_guard"]]
    assert acts == ["defer_to_11", "pass"]
    assert res.history["expansion_steps"] == [11]
    assert res.final_layers == 2
    assert math.isfinite(res.history["loss"][-1])


def test_expansion_guard_clean_run_no_false_positive(tmp_path):
    res = run(_guard_tcfg("copying_zeroL"), ckpt_dir=str(tmp_path),
              expansion_guard=True, guard_window=5).run()
    acts = [e["action"] for e in res.history["expansion_guard"]]
    assert acts == ["pass"]
    assert res.history["expansion_steps"] == [8]


# ---------------------------------------------------------------------------
# Async checkpointer under crash (satellite: torn-write, async path)
# ---------------------------------------------------------------------------


def test_async_crash_before_manifest_keeps_previous_latest(tmp_path):
    d = str(tmp_path)
    tree = {"w": np.arange(4, dtype=np.float32)}
    ckpt.save(d, 1, tree)
    ac = ckpt.AsyncCheckpointer()
    ac.save(d, 2, tree, faults=FaultPlane.parse("ckpt.write:1:crash"))
    with pytest.raises(CrashError):
        ac.wait()
    assert ckpt.latest_step(d) == 1                    # torn dir invisible
    assert os.path.isdir(os.path.join(d, "step_000000002.tmp"))
    restored = ckpt.restore(d, 1, {"w": tree["w"]})
    assert np.array_equal(restored["w"], tree["w"])
    ac.save(d, 2, tree)                                # clean write sweeps
    ac.wait()
    assert ckpt.latest_step(d) == 2
    assert not os.path.exists(os.path.join(d, "step_000000002.tmp"))


# ---------------------------------------------------------------------------
# StragglerMonitor hang deadline
# ---------------------------------------------------------------------------


def test_straggler_monitor_hang_deadline_unit():
    mon = StragglerMonitor(hang_deadline_s=0.0)
    mon.start()
    with pytest.raises(HangError) as ei:
        mon.stop()
    assert isinstance(ei.value, FaultError)       # contained as train.step
    assert not isinstance(ei.value, CrashError)
    assert ei.value.site == "train.step"
    assert mon.hangs == 1 and mon.last_dt > 0.0


def test_engine_contains_hangs_and_completes(clean_result):
    """Deadline 0 flags every step as hung; the trainer records each hang
    and keeps going — crucially WITHOUT retrying the (donated) step."""
    res = run(hang_deadline_s=0.0).run()
    assert res.history["hangs"] == list(range(12))
    assert res.history["loss"] == clean_result.history["loss"]
    assert leaves_equal(res.params, clean_result.params)
