"""Tiny cells for the CPU tests: the cells' own mixes and limits, at a
model and batch a test run can hold."""
import argparse
import copy
import json
from pathlib import Path

import harness

ROOT = Path(__file__).resolve().parents[2]
TINY = {"name": "tiny", "family": "dense", "num_layers": 2, "d_model": 64,
        "num_heads": 4, "num_kv_heads": 4, "head_dim": 16, "d_ff": 256,
        "vocab_size": 512, "max_seq_len": 128, "attention": "mha",
        "activation": "gelu", "norm": "layernorm", "position": "absolute",
        "tie_embeddings": True}
PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2 ** 30}


def cell(name: str) -> harness.Cell:
    """The cell ``name`` as BENCHMARK.json has it, shrunk: tiny widths,
    short sequences, a small batch; its limits and metrics unchanged."""
    c = copy.deepcopy(harness.load_cell(name, root=ROOT))
    mix = c.traffic
    fixed = mix["source_layers"] >= c.model["num_layers"]
    c.config = dict(c.config, model=dict(TINY))
    mix.update(seq_len=32, batch=8, corpus_tokens=20000)
    mix["source_layers"] = TINY["num_layers"] if fixed else min(
        mix["source_layers"], TINY["num_layers"] - 1)
    c.workload = dict(c.workload, step_s=0.05)
    return c


def args(seed=2 ** 31 + 11, seconds=0.5, trace=0):
    return argparse.Namespace(seed=seed, seconds=seconds, trace=trace)


def run(c, a, capsys):
    """Run the cell in this process on the CPU; the parsed result line."""
    import jax
    import run as run_mod
    run_mod.run_cell(c, a, jax.devices()[:1], PEAK)
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])
