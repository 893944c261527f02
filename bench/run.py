#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload gpt2-12l.train-fixed --seed 7 \
        --seconds 51 --trace 0

One process: set up (seeded data and weights, compile or load every
program the cell's traffic uses), measure for ``--seconds``, check the
output against the plain reference, print.  ``--trace 0`` reports the
cell's end-to-end metrics; ``--trace 1`` traces the window and reports its
per-layer metrics instead.  The last line of standard output is one JSON
object (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
``breakdown`` when traced, and last ``checks``: every compared number with
its limit, which are also the last lines of standard error).

Exits 2 without the program's ``src/`` beside this directory, 3 when JAX
finds no accelerator or fewer chips than the cell asks for, 4 for a
device the peak table does not list; none of these prints a result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no src/repro beside {BENCH}; run it from a checkout "
              f"of the repository", file=sys.stderr)
        return 2
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import harness
    cell = harness.load_cell(args.workload)

    import jax
    from repro.launch import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = harness.check_devices(cell.chips)
    import counts
    try:
        peak = counts.peaks(devices[0].device_kind)
    except counts.UnknownDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 4
    return run_cell(cell, args, devices, peak)


def run_cell(cell, args, devices, peak) -> int:
    import harness
    import tracing as trace_lib
    kind = importlib.import_module("kinds." + cell.traffic["kind"])
    trace_dir = Path(tempfile.mkdtemp(prefix="bench-trace-")) \
        if args.trace else None
    spans = harness.Spans(traced=bool(args.trace))
    window = harness.Window(trace_dir)
    try:
        out = kind.run(cell, args, devices, window, spans)
        setup_s = window.t0 - T_START
        red = None
        if trace_dir is not None:
            red = trace_lib.Reduction(trace_lib.load(trace_dir))
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    correct, checks = harness.judge(out["values"], cell.workload["limits"])
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": bool(correct), "attempted": out["attempted"],
              "failed": out["failed"]}
    if red is None:
        e2e = dict(out["e2e"], setup_s=setup_s)
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    else:
        run = Run(cell, args, out, red, peak, len(devices))
        metrics = {}
        for m in cell.per_layer:
            v = harness.load_reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        device.update(busy_s=red.busy_s(), window_s=red.window_s)
        result["breakdown"] = {"device_ops": red.top_ops(10),
                               "idle_gaps": red.idle_gaps(10)}
    result["device"] = device
    harness.emit(result, checks)
    return 0


class Run:
    """What a per-layer reader reads: the cell, the run's own counts and
    host timings (``stats``), the trace's reduction, and the peaks."""

    def __init__(self, cell, args, out, trace, peak, chips):
        self.cell = cell
        self.args = args
        self.stats = out["stats"]
        self.trace = trace
        self.peak = peak
        self.chips = chips


if __name__ == "__main__":
    sys.exit(main())
