"""The readers of the program's own spans (``program_spans`` and the
metrics ``fetch_ms.train``, ``compile_s.train``, ``expand_idle_s``): on
hand-built logs and traces, on a program without spans, and on tiny traced
runs recorded on the CPU."""
import math
import sys
import types

import pytest

import bench_tiny
import harness
import program_spans
import tracing

NEW = ("fetch_ms.train", "compile_s.train", "expand_idle_s")
SHIFT = 5_000_000       # trace clock = program clock + SHIFT, in ns


def _run(log, ops, host, fetches):
    """A traced run as the readers see it: a trace of one device with
    ``ops``, host events ``host`` and the benchmark's fetch clock readings,
    and the program's log ``log``."""
    trace = {"devices": {"/device:TPU:0": {"ops": [list(o) for o in ops]}},
             "host": [list(h) for h in host]}
    return types.SimpleNamespace(trace=tracing.Reduction(trace),
                                 stats={"fetches": fetches}), log


def _hand_built():
    # program clock (ns); the batch fetches begin at 100, 1100, 2100, 3100
    log = [("train.fetch", None, 100, 120, {}),
           ("train.dispatch", None, 130, 200, {}),
           ("train.fetch", None, 1100, 1140, {}),
           ("train.dispatch", None, 1150, 1200, {}),
           ("train.expand", None, 1300, 1800, {}),
           ("jax.trace", 4, 1350, 1500, {"fun_name": "expand_fn"}),
           ("jax.trace", 4, 1400, 1450, {"fun_name": "inner"}),
           ("jax.compile", 4, 1600, 1700, {"fun_name": "expand_fn",
                                           "cache_hit": True}),
           ("train.fetch", None, 2100, 2160, {}),
           ("train.dispatch", None, 2200, 2900, {}),
           ("jax.lower", 9, 2300, 2600, {"fun_name": "step"}),
           ("train.fetch", None, 3100, 3120, {}),
           ("train.dispatch", None, 3130, 3200, {}),
           ("train.fetch", None, 9000, None, {})]      # still open
    host = [("bench.window", SHIFT + 50, 3500), ("bench.batch", SHIFT + 101,
                                                 10)]
    host += [("bench.batch", SHIFT + t, 10) for t in (1100, 2099, 3100)]
    # the device runs until 1250, in the expansion at 1550-1650, and from
    # 2700 on (program clock)
    ops = [("fusion", SHIFT + 50, 1200), ("expand", SHIFT + 1550, 100),
           ("fusion", SHIFT + 2700, 850)]
    return _run(log, ops, host, [t / 1e9 for t in (100, 1100, 2100, 3100)])


@pytest.fixture
def program_log(monkeypatch):
    """Stand the program's log in with a given list."""
    from repro import spans

    def use(log):
        monkeypatch.setattr(spans, "log", lambda: list(log))
    return use


def test_hand_built_log_maps_onto_the_trace_and_reads(program_log):
    run, log = _hand_built()
    program_log(log)
    mapped = program_spans.window_spans(run)
    assert len(mapped) == len(log) - 1       # the open span is left out
    assert mapped[0][:3] == ("train.fetch", SHIFT + 100, SHIFT + 120)
    read = {m: harness.load_reader(m)(run) for m in NEW}
    assert read["fetch_ms.train"] == pytest.approx(35e-6)
    # jax spans 1350-1500 (1400-1450 inside it), 1600-1700, 2300-2600
    assert read["compile_s.train"] == pytest.approx(550e-9)
    # expansion 1300-1800 and the next dispatch 2200-2900; idle in them:
    # 1300-1550, 1650-1800, 2200-2700
    assert read["expand_idle_s"] == pytest.approx(900e-9)


def test_nothing_to_read_without_the_program_spans(monkeypatch):
    """Over a program that has no ``repro.spans``, every reader returns
    None and none raises."""
    import repro
    run, _ = _hand_built()
    monkeypatch.delattr(repro, "spans", raising=False)
    monkeypatch.setitem(sys.modules, "repro.spans", None)
    assert program_spans.window_spans(run) is None
    for m in NEW:
        assert harness.load_reader(m)(run) is None


def test_nothing_to_read_without_an_expansion_or_a_device(program_log):
    run, log = _hand_built()
    program_log([r for r in log if r[0] != "train.expand"])
    assert harness.load_reader("expand_idle_s")(run) is None
    program_log(log)
    run.trace = tracing.Reduction({"devices": {},
                                   "host": run.trace.trace["host"]})
    assert program_spans.idle(run) is None
    assert harness.load_reader("expand_idle_s")(run) is None


def _step_spans_as_a_device(monkeypatch):
    """The CPU's trace has no device plane: give it one that runs exactly
    while the benchmark's step call is open."""
    load = tracing.load

    def with_device(log_dir):
        t = load(log_dir)
        t["devices"] = {"/device:TPU:0": {"ops": [
            ["step", s, d] for n, s, d in t["host"] if n == "bench.step"]}}
        return t
    monkeypatch.setattr(tracing, "load", with_device)


@pytest.mark.parametrize("name", ["gpt2-12l.train-fixed",
                                  "gpt2-12l.train-prog"])
def test_tiny_traced_run_reports_program_spans(name, monkeypatch, capsys):
    import run as run_mod
    from repro import spans
    runs = []

    class Kept(run_mod.Run):
        def __init__(self, *a):
            super().__init__(*a)
            runs.append(self)

    monkeypatch.setattr(run_mod, "Run", Kept)
    _step_spans_as_a_device(monkeypatch)
    spans.clear()
    res = bench_tiny.run(bench_tiny.cell(name), bench_tiny.args(trace=1),
                         capsys)
    assert res["correct"], res["checks"]
    want = NEW if name.endswith("train-prog") else NEW[:1]
    for m in NEW:
        assert (m in res["metrics"]) == (m in want), m
    for m in want:
        v = res["metrics"][m]["value"]
        assert math.isfinite(v) and v > 0, (m, v)
    # each mapped train.fetch encloses its bench.batch event to 50 us
    run = runs[0]
    fetch = program_spans.named(program_spans.window_spans(run),
                                "train.fetch")
    batch = sorted([s, s + d] for n, s, d in run.trace.trace["host"]
                   if n == "bench.batch")
    assert len(fetch) == len(batch) == len(run.stats["fetches"])
    for (fs, fe), (bs, be) in zip(fetch, batch):
        assert fs - 50_000 <= bs and be <= fe + 50_000
    spans.clear()
