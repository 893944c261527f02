"""What every cell shares: finding its files, spans, the device, the result.

A cell is found by name in ``BENCHMARK.json``; its configuration, its
traffic mix and its own numbers (rates, limits) are data files under
``configs/``, ``traffic/`` and ``workloads/``; its kind (``train`` or
``serve``, named by the mix) is a module under ``kinds/``; each per-layer
metric is a reader ``metrics/<metric>.py`` with ``read(run) -> float |
None``.  Adding a cell, a mix, a configuration or a metric adds files and
entries and edits none.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import sys
import time
from pathlib import Path
from typing import Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    workload: dict
    end_to_end: list
    per_layer: list

    @property
    def model(self) -> dict:
        return self.config["model"]


def _reports(metric: dict, cell: str, e2e_names) -> bool:
    """A metric with a ``workloads`` list is reported by those cells; a
    per-layer one without it by every cell that reports what it moves; an
    end-to-end one without it by every cell."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_names


def load_cell(name: str, bench: Optional[dict] = None,
              root: Path = ROOT) -> Cell:
    bench = bench if bench is not None else load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; BENCHMARK.json has "
                       f"{sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"] if _reports(m, name, ())]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, name, e2e_names)]
    bench_dir = root / "bench"
    return Cell(name=name, chips=int(w["chips"]),
                config=load_json(root / conf["file"]),
                traffic=load_json(bench_dir / "traffic"
                                  / f"{w['traffic']}.json"),
                workload=load_json(bench_dir / "workloads" / f"{name}.json"),
                end_to_end=e2e, per_layer=per_layer)


def load_reader(metric: str, bench: Path = BENCH):
    path = bench / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def key_seed(seed: int) -> int:
    """The run's seed as a JAX key seed (keys hold 32 bits)."""
    return int(seed) % (1 << 32)


class Spans:
    """Host spans around the benchmark's calls into the program: durations
    on the host clock, and, while a trace runs, ``bench.<name>`` events in
    it on the device's clock."""

    def __init__(self, traced: bool = False):
        self.traced = traced
        self.durations: dict = {}

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.traced:
            import jax
            ann = jax.profiler.TraceAnnotation("bench." + name)
            ann.__enter__()
        t = time.perf_counter()
        try:
            yield
        finally:
            self.durations.setdefault(name, []).append(
                time.perf_counter() - t)
            if ann is not None:
                ann.__exit__(None, None, None)


class Window:
    """The measured window: host-clock bounds and, in a traced run, the
    profiler trace around it."""

    def __init__(self, trace_dir: Optional[Path] = None):
        self.trace_dir = trace_dir
        self.t0 = self.t1 = None
        self._ann = None

    def open(self):
        if self.trace_dir is not None:
            import jax
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=opts)
            self._ann = jax.profiler.TraceAnnotation("bench.window")
            self._ann.__enter__()
        self.t0 = time.perf_counter()

    def close(self):
        self.t1 = time.perf_counter()
        if self._ann is not None:
            import jax
            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def check_devices(chips: int):
    """The devices a cell runs on, or SystemExit when JAX finds no
    accelerator or too few chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu" or len(devs) < chips:
        print(f"bench: needs {chips} accelerator chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        raise SystemExit(3)
    return devs[:chips]


def emit(result: dict, checks: dict):
    """The run's last lines: each compared number beside its limit on
    standard error, then the result as one JSON line on standard out, with
    the same numbers as its last key."""
    result = dict(result)
    result["checks"] = checks
    sys.stdout.flush()
    for k, c in checks.items():
        ok = c["value"] is not None and c["value"] <= c["limit"]
        print(f"check {k}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if ok else 'FAIL'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def judge(values: dict, limits: dict) -> tuple:
    """(correct, checks): every number at or under its limit."""
    checks = {k: {"value": values.get(k), "limit": limits[k]}
              for k in limits}
    ok = all(c["value"] is not None and math.isfinite(c["value"])
             and c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
