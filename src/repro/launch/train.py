"""Training CLI.

    PYTHONPATH=src python -m repro.launch.train \
        --arch gpt2-12l --source-layers 1 --tau 0.8 --init random \
        --steps 1000 --seq-len 256 --batch 16 --schedule wsd \
        --optimizer muon_nsgd --lr 0.01 --ckpt-dir /tmp/run1

Runs the paper's progressive recipe end-to-end on the selected architecture
(reduced sizes run on CPU; production meshes take the same code path via
--mesh prod on a TPU slice)."""
from __future__ import annotations

import argparse
import json

FAULT_GRAMMAR = """\
fault spec grammar (shared with launch/serve.py — one FaultPlane.parse):
  site:nth[:kind],...   the nth (1-based) hit of a named site raises; kind
                        is 'fault' (transient, retried/contained) or 'crash'
                        (process death — resume from --ckpt-dir to recover)
  storm:rate[:seed]     seeded Bernoulli fault storm over all non-iteration
                        sites
train-side sites: train.batch train.step train.eval train.expand train.iter
                  ckpt.write ckpt.restore   (train.iter = scheduled-crash
                  point, e.g. train.iter:40:crash)
example: --faults ckpt.write:1,train.iter:120:crash --nan-policy skip
"""

from repro import configs as cfglib
from repro.configs.base import (ExpansionConfig, OptimizerConfig,
                                ScheduleConfig, TrainConfig)
from repro.launch import compile_cache
from repro.launch import mesh as mesh_lib
from repro.train import loop


def main(argv=None):
    """Run the CLI; returns the ``TrainResult``."""
    ap = argparse.ArgumentParser(
        epilog=FAULT_GRAMMAR,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--arch", default="gpt2-12l")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config for --arch")
    ap.add_argument("--source-layers", type=int, default=1)
    ap.add_argument("--tau", type=float, default=0.8,
                    help="expansion point as fraction of total steps; "
                    "<=0 disables expansion (fixed-size training)")
    ap.add_argument("--init", default="random",
                    choices=["random", "zero", "copying_stack",
                             "copying_inter", "copying_last",
                             "copying_zeroL", "copying_zeroN"])
    ap.add_argument("--os-policy", default="inherit",
                    choices=["inherit", "copy", "reset"])
    ap.add_argument("--steps", type=int, default=500)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--schedule", default="wsd", choices=["wsd", "cosine",
                                                          "constant"])
    ap.add_argument("--optimizer", default="muon_nsgd",
                    choices=["muon_nsgd", "adamw", "nsgd", "sgd"])
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--history-out", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--remat", nargs="?", const="auto", default="off",
                    choices=["off", "auto", "nothing", "dots"],
                    help="activation checkpointing: bare --remat picks the "
                    "arch's measured policy (configs.REMAT_DEFAULTS); "
                    "'nothing' recomputes every layer but the flash "
                    "kernel's output, 'dots' also saves matmul outputs")
    ap.add_argument("--mesh", default="single",
                    help="mesh spec: single | host | prod | prod-multipod "
                    "| AxB (data x model), e.g. 4x2")
    ap.add_argument("--grad-accum", type=int, default=1,
                    help="microbatches per step (gradient accumulation); "
                    "must divide --batch")
    ap.add_argument("--faults", default=None, metavar="SPEC",
                    help="deterministic fault injection (see grammar below)")
    ap.add_argument("--nan-policy", default="off",
                    choices=["off", "warn", "skip", "rollback"],
                    help="bad-step sentinel ladder: warn logs, skip discards "
                    "the update on device, rollback also restores the "
                    "latest checkpoint after repeated bad steps")
    ap.add_argument("--nan-inject", default=None, metavar="SPEC",
                    help="numerical fault injection 'kind:step[@attempt],...'"
                    " with kind nan|spike (testing the sentinels)")
    ap.add_argument("--expansion-guard", action="store_true",
                    help="post-expansion divergence watchdog: auto-rollback "
                    "to the boundary checkpoint and retry with a "
                    "function-preserving init / deferred tau")
    ap.add_argument("--retries", type=int, default=2,
                    help="max retries per transient fault site")
    ap.add_argument("--hang-deadline-s", type=float, default=None,
                    help="fail a train step as a train.step fault if it "
                    "exceeds this wall time instead of stalling")
    args = ap.parse_args(argv)
    compile_cache.enable()

    cfg = (cfglib.get_smoke_config(args.arch) if args.smoke
           else cfglib.get_config(args.arch))
    period = cfg.pattern_period
    src = args.source_layers - args.source_layers % period \
        if args.source_layers >= period else 0
    expansions = ()
    if args.tau > 0:
        expansions = (ExpansionConfig(at_frac=args.tau,
                                      target_layers=cfg.num_layers,
                                      init=args.init,
                                      opt_state_policy=args.os_policy),)
    else:
        src = cfg.num_layers
    tcfg = TrainConfig(
        total_steps=args.steps, seq_len=args.seq_len, global_batch=args.batch,
        grad_accum=args.grad_accum, source_layers=src, expansions=expansions,
        optimizer=OptimizerConfig(name=args.optimizer, learning_rate=args.lr),
        schedule=ScheduleConfig(name=args.schedule),
        seed=args.seed,
        remat=(False if args.remat == "off"
               else cfglib.default_remat(args.arch) if args.remat == "auto"
               else args.remat))
    mesh = mesh_lib.make_train_mesh(args.mesh)
    res = loop.train(cfg, tcfg, checkpoint_dir=args.ckpt_dir, mesh=mesh,
                     faults=args.faults, nan_policy=args.nan_policy,
                     nan_inject=args.nan_inject,
                     expansion_guard=args.expansion_guard,
                     max_retries=args.retries,
                     hang_deadline_s=args.hang_deadline_s)
    print(f"final loss: {res.history['loss'][-1]:.4f} "
          f"(layers {res.final_layers})")
    fs = res.fault_stats
    if (args.faults or args.nan_policy != "off" or args.nan_inject
            or args.expansion_guard or args.hang_deadline_s is not None):
        print(f"faults: retries={fs['retries']} "
              f"ckpt_failures={fs['ckpt_failures']} "
              f"skipped={fs['skipped_steps']} "
              f"nan_rollbacks={fs['nan_rollbacks']} "
              f"guard_events={fs['guard_events']} hangs={fs['hangs']} "
              f"site_hits={fs['fault_counts']} fired={fs['fired']}")
    if args.history_out:
        with open(args.history_out, "w") as f:
            json.dump(res.history, f)
    return res


if __name__ == "__main__":
    main()
