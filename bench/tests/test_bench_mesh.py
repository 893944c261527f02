"""The 2x2 cell (``gpt2-60l-15of60.train-2x2``): its reference placed over
the cell's chips by ``kinds/place.py``, a tiny run of the cell on four CPU
devices, its counts at the published widths, and its two new readers."""
import json
import math
import os
import subprocess
import sys
import types
from pathlib import Path

import jax
import pytest
from jax.sharding import PartitionSpec as P

import harness
import tracing
from kinds import place, train

BENCH = Path(__file__).resolve().parents[1]
CELL = "gpt2-60l-15of60.train-2x2"

# A tiny run of the cell in a process of its own, which is given four CPU
# devices (this one may have only one): head_dim 64, so that tensor
# parallelism splits whole heads.  It prints the result line and the
# placement of the reference's weights.
RUN_TINY = """
import json, sys
sys.path[:0] = [{bench!r}, {tests!r}, {src!r}]
import jax
import bench_tiny, run
from kinds import train
cell = bench_tiny.cell({cell!r})
cell.config["model"] = dict(bench_tiny.TINY, d_model=128, num_heads=2,
                            num_kv_heads=2, head_dim=64)
w = train.weights_fn(cell, 5, cell.traffic["source_layers"])()
print(json.dumps(sorted({{len(x.sharding.device_set)
                         for x in jax.tree.leaves(w)}})))
run.run_cell(cell, bench_tiny.args(), jax.devices()[:4], bench_tiny.PEAK)
"""


@pytest.mark.parametrize("shape,want", [
    ((15, 3072, 12288), P(None, None, place.AXIS)),
    ((15, 12288, 3072), P(None, place.AXIS, None)),
    ((15, 3072, 3072), P(None, None, place.AXIS)),     # the last of equals
    ((50304, 3072), P(place.AXIS, None)),
    ((15, 3072), P(None, place.AXIS)),
    ((8, 15), P(place.AXIS, None)),     # 15 not divisible: the next largest
    ((3, 5), P()),                      # none divisible: whole on each chip
])
def test_reference_leaf_split_along_its_largest_divisible_dim(shape, want):
    assert place.spec(shape, 4) == want


def test_one_chip_cells_keep_their_reference_weights_whole():
    cell = harness.load_cell("gpt2-12l.train-fixed")
    cell.config = dict(cell.config, model=dict(cell.model, num_layers=1,
                                               d_model=64, num_heads=1,
                                               num_kv_heads=1, d_ff=64,
                                               vocab_size=128,
                                               max_seq_len=16))
    w = train.weights_fn(cell, 3, 1)()
    assert {len(x.sharding.device_set) for x in jax.tree.leaves(w)} == {1}


def test_multi_chip_reference_steps_give_the_one_chip_numbers():
    """The multi-chip order of the reference's first steps computes what
    the one-chip order does, bit for bit (both on one device here), in
    float32 and in the bfloat16 control."""
    import bench_tiny
    cell = bench_tiny.cell("gpt2-12l.train-fixed")
    corpus = train.Corpus(cell, 7)
    try:
        for dtype in (jax.numpy.float32, jax.numpy.bfloat16):
            want = train.reference_steps(cell, 7, 20, corpus, dtype=dtype)
            got = place.reference_steps(train, cell, 7, 20, corpus,
                                        dtype=dtype)
            assert got == want
    finally:
        corpus.close()


def test_tiny_2x2_cell_runs_correct_with_the_reference_over_four_devices():
    src = str(BENCH.parent / "src")
    code = RUN_TINY.format(bench=str(BENCH), tests=str(BENCH / "tests"),
                           src=src, cell=CELL)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300,
                         cwd=str(BENCH.parent))
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    assert json.loads(lines[0]) == [4]
    res = json.loads(lines[-1])
    assert res["correct"], res["checks"]
    assert res["device"]["count"] == 4
    assert res["checks"]["depth_errors"]["value"] == 0


def test_counts_agree_with_the_program_at_the_published_widths():
    """The program's parameter tree of one 15-layer stage (shapes only):
    1,856,563,200 parameters, each layer's matrices those
    ``layer_matmul_params`` counts, and the matrices Muon orthogonalizes
    those ``muon_matrices`` lists (q, k and v are leaves of their own)."""
    import counts
    from repro.configs.base import ModelConfig
    from repro.configs.gpt2 import gpt2
    from repro.models import registry
    from repro.optim import muon
    m = harness.load_cell(CELL).model
    cfg = gpt2(60).with_depth(15)
    assert ModelConfig(**m) == cfg
    api = registry.get_model(cfg)
    tree = jax.eval_shape(lambda k: api.init(k, cfg), jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    total = sum(math.prod(x.shape) for _, x in leaves)
    assert total == 1_856_563_200
    d, layers = m["d_model"], m["num_layers"]
    assert total == (layers * (counts.layer_matmul_params(m) + 4 * d)
                     + (m["vocab_size"] + m["max_seq_len"] + 2) * d)
    ortho = []
    for path, x in leaves:
        if muon._is_matrix(path, x):
            ortho += [tuple(x.shape[-2:])] * (x.shape[0] if muon._stacked(
                path) else 1)
    assert sorted(ortho) == sorted(counts.muon_matrices(m, layers))
    per_layer = [math.prod(x.shape[1:]) for p, x in leaves
                 if muon._stacked(p) and muon._is_matrix(p, x)]
    assert sum(per_layer) == counts.layer_matmul_params(m)


def _run(ops_by_device, window=(0, 100)):
    trace = {"devices": {d: {"ops": [list(o) for o in ops]}
                         for d, ops in ops_by_device.items()},
             "host": [["bench.window", window[0], window[1] - window[0]]]}
    return types.SimpleNamespace(trace=tracing.Reduction(trace))


def test_collective_exposed_share_averages_the_devices():
    read = harness.load_reader("collective_exposed_share.train")
    run = _run({"/device:TPU:0": [("fusion", 0, 40), ("all-gather", 30, 30)],
                "/device:TPU:1": [("fusion", 0, 40),
                                  ("all-reduce-start", 40, 10),
                                  ("while", 0, 100)]})
    # device 0: 40-60 exposed (20); device 1: 40-50 (10); the while loop
    # that holds them is not compute
    assert read(run) == pytest.approx(15.0)
    assert read(_run({})) is None


def test_init_seconds_read_from_the_program_or_nothing(monkeypatch):
    from repro import spans
    read = harness.load_reader("init_s.train")
    with spans.span("train.init") as s:
        pass
    assert read(None) == s.seconds > 0
    monkeypatch.delattr(spans, "last")      # a program without the table
    assert read(None) is None
