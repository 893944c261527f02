"""The trace-to-metric reduction, on hand-built events and on a small
trace recorded on one v5e chip (training step and paged serving)."""
import json
from pathlib import Path

import pytest

import tracing

DATA = json.loads((Path(__file__).parent / "data" /
                   "trace_small.json").read_text())


def _trace(ops, host=()):
    return {"devices": {"/device:TPU:0": {"ops": [list(o) for o in ops]}},
            "host": [list(h) for h in host]}


def test_base_names():
    assert tracing.base_name(
        "%flash_attention_fwd.14 = (f32[32,12,1024,64]) custom-call(...)") \
        == "flash_attention_fwd"
    assert tracing.base_name("%while.7 = (s32[]) while(...)") == "while"
    assert tracing.base_name("copy.3") == "copy"


def test_hand_built_busy_idle_gaps_and_attribution():
    ops = [("a", 0, 10), ("b", 5, 10), ("c", 30, 10), ("d", 70, 30)]
    host = [("bench.window", 0, 100), ("bench.batch", 14, 20),
            ("bench.expand", 41, 28)]
    red = tracing.Reduction(_trace(ops, host=host))
    assert red.window_s == pytest.approx(100e-9)
    assert red.busy_s() == pytest.approx(55e-9)
    assert red.idle_share() == pytest.approx(0.45)
    gaps = red.idle_gaps()
    assert gaps == [["expand", pytest.approx(30e-9)],
                    ["batch", pytest.approx(15e-9)]]
    assert red.op_s(lambda n: n in ("a", "b")) == pytest.approx(20e-9)


def test_hand_built_self_time():
    ops = [("while", 0, 100), ("k", 10, 30), ("f", 50, 20), ("g", 55, 5),
           ("k", 200, 10)]
    red = tracing.Reduction(_trace(ops), window=(0, 300))
    assert dict(red.top_ops()) == pytest.approx(
        {"while": 50e-9, "k": 40e-9, "f": 15e-9, "g": 5e-9})


def test_hand_built_exposed_collectives():
    ops = [("all-gather-start", 0, 40), ("fusion", 10, 10),
           ("reduce-scatter", 60, 20), ("fusion", 70, 30)]
    red = tracing.Reduction(_trace(ops), window=(0, 100))
    assert red.exposed_collective_s() == pytest.approx((30 + 10) * 1e-9)


@pytest.mark.parametrize("tag", ["train", "serve"])
def test_recorded_trace_invariants(tag):
    red = tracing.Reduction(DATA[tag])
    ops = DATA[tag]["devices"]["/device:TPU:0"]["ops"]
    assert 0 < red.busy_s() <= red.window_s
    assert 0 <= red.idle_share() < 1
    # self times split the busy time among the ops without double counting
    assert sum(t for _, t in tracing.self_times(ops)) == \
        pytest.approx(red.busy_s(), rel=1e-9)
    top = red.top_ops(10)
    assert [t for _, t in top] == sorted((t for _, t in top), reverse=True)
    assert len(red.idle_gaps(10)) <= 10


def test_recorded_training_step_kernels():
    red = tracing.Reduction(DATA["train"])
    ops = DATA["train"]["devices"]["/device:TPU:0"]["ops"]
    names = {n for n, _, _ in ops}
    assert {"flash_attention_fwd", "flash_attention_dq",
            "flash_attention_dkv"} <= names
    want = sum(d for n, _, d in ops if n.startswith("flash_attention"))
    assert red.op_s(lambda n: n.startswith("flash_attention")) == \
        pytest.approx(want / 1e9)
