"""Whole runs of the harness on the CPU at tiny sizes: sound runs come out
correct, and each fault the cells can have, planted in the program under
the timed path, makes ``correct`` false.  (Only the look for a chip is
skipped.)"""
import dataclasses
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import bench_tiny

TRAIN = ["gpt2-12l.train-fixed", "gpt2-12l.train-prog"]
EXPANSION = ["depth_errors", "expand_exact_diff", "new_layer_init_z",
             "deep_grad_gap"]


@pytest.mark.parametrize("name,source", [(TRAIN[0], None), (TRAIN[1], None),
                                         (TRAIN[1], 0)])
def test_training_cell_runs_correct(name, source, capsys):
    cell = bench_tiny.cell(name)
    if source is not None:      # the zero-layer recipe through the same check
        cell.traffic["source_layers"] = source
    res = bench_tiny.run(cell, bench_tiny.args(), capsys)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert res["metrics"]["train_tokens_per_s"]["value"] > 0
    grows = name.endswith("train-prog")
    assert (set(EXPANSION) <= set(res["checks"])) == grows
    assert res["checks"]["depth_errors"]["value"] == 0


def test_training_traced_run_reports_per_layer_metrics(capsys):
    res = bench_tiny.run(bench_tiny.cell(TRAIN[1]),
                         bench_tiny.args(trace=1), capsys)
    assert res["correct"], res["checks"]
    assert "expand_s" in res["metrics"] and "input_ms.train" in \
        res["metrics"]
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_fault_step_returns_state_unchanged(monkeypatch, capsys):
    from repro.train import steps
    orig = steps.make_train_step

    def frozen(*a, **k):
        fn = orig(*a, **dict(k, donate=False))

        def step(params, opt_state, batch, i, *rest):
            return params, opt_state, fn(params, opt_state, batch, i,
                                         *rest)[2]
        return step

    monkeypatch.setattr(steps, "make_train_step", frozen)
    res = bench_tiny.run(bench_tiny.cell(TRAIN[0]), bench_tiny.args(),
                         capsys)
    assert not res["correct"]
    assert res["checks"]["change_gap"]["value"] == pytest.approx(1.0)


def _half_batch(monkeypatch, deep_only=False):
    """Plant the loss over half of each batch, the mean taken over the
    rest: at every depth, or only in steps at the full depth of the tiny
    model."""
    from repro.models import registry
    orig = registry._lm_loss

    def half(params, cfg, batch, remat=False):
        if deep_only and cfg.num_layers < bench_tiny.TINY["num_layers"]:
            return orig(params, cfg, batch, remat=remat)
        n = batch["tokens"].shape[0] // 2
        return orig(params, cfg, {k: v[:n] for k, v in batch.items()},
                    remat=remat)

    monkeypatch.setattr(registry, "_lm_loss", half)


def test_fault_half_the_batch_left_out(monkeypatch, capsys):
    _half_batch(monkeypatch)
    res = bench_tiny.run(bench_tiny.cell(TRAIN[0]), bench_tiny.args(),
                         capsys)
    assert not res["correct"], res["checks"]


def test_fault_half_the_batch_left_out_after_the_expansion(monkeypatch,
                                                           capsys):
    _half_batch(monkeypatch, deep_only=True)
    res = bench_tiny.run(bench_tiny.cell(TRAIN[1]), bench_tiny.args(),
                         capsys)
    checks = res["checks"]
    assert not res["correct"], checks
    for k in ("loss_gap", "grad_gap", "change_gap", "expand_exact_diff"):
        assert checks[k]["value"] <= checks[k]["limit"], k
    assert checks["deep_grad_gap"]["value"] > checks["deep_grad_gap"]["limit"]


def _plant_expansion(monkeypatch, fault):
    """Plant one fault in the program's depth expansion."""
    from repro.core import expansion
    from repro.train import engine
    if fault == "late":
        orig_init = engine.ProgressiveTrainer.__init__

        def late(self, model_cfg, tcfg, *a, **k):
            tcfg = dataclasses.replace(tcfg, expansions=tuple(
                dataclasses.replace(e, at_frac=1.0)
                for e in tcfg.expansions))
            orig_init(self, model_cfg, tcfg, *a, **k)
        monkeypatch.setattr(engine.ProgressiveTrainer, "__init__", late)
    elif fault == "reset_momentum":
        orig = expansion.expand_opt_state
        monkeypatch.setattr(expansion, "expand_opt_state",
                            lambda s, p, policy, *a, **k:
                            orig(s, p, "reset", *a, **k))
    else:
        orig = expansion.expand_params
        kw = {"zero_init": {"method": "zero"},
              "copy_init": {"method": "copying_last"},
              "new_layers_first": {"insert_at": "top"}}[fault]

        def planted(params, cfg, target_layers, method, **k):
            k.update(kw)
            return orig(params, cfg, target_layers,
                        k.pop("method", method), **k)
        monkeypatch.setattr(expansion, "expand_params", planted)


@pytest.mark.parametrize("fault", ["late", "reset_momentum", "zero_init",
                                   "copy_init", "new_layers_first"])
def test_fault_in_the_expansion(fault, monkeypatch, capsys):
    _plant_expansion(monkeypatch, fault)
    res = bench_tiny.run(bench_tiny.cell(TRAIN[1]), bench_tiny.args(),
                         capsys)
    checks = res["checks"]
    assert not res["correct"], checks
    failed = {k for k, c in checks.items()
              if c["value"] is None or c["value"] > c["limit"]}
    want = {"late": "depth_errors", "reset_momentum": "expand_exact_diff",
            "zero_init": "new_layer_init_z", "copy_init": "expand_exact_diff",
            "new_layers_first": "expand_exact_diff"}[fault]
    assert want in failed, checks


def _run_py(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", TRAIN[0], "--seed",
         "1", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_no_result_without_a_chip_or_without_the_program(tmp_path):
    root = Path(bench_tiny.ROOT)
    p = _run_py(root)
    assert p.returncode == 3 and p.stdout == ""
    shutil.copytree(root / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(root / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _run_py(tmp_path)
    assert p.returncode == 2 and p.stdout == ""
