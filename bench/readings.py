#!/usr/bin/env python3
"""The numbers ``correct`` compares, read on many seeds in one process,
for setting a training cell's limits (not part of a cell's run).

    python3 bench/readings.py --workload gpt2-12l.train-prog --seconds 51 \
        --seeds 1,2,3 --modes program,bf16,half_batch,copy_init

``program`` reads the program's numbers against the reference (the lower
readings): its first steps, and in a progressive mix its expansion and
first deep step; ``bf16`` puts the reference, computed in bfloat16, in the
program's place (the low-precision control); ``half_batch`` puts the
reference, taking its gradient over half of each batch, in its place (a
planted fault); ``copy_init`` reads the init number of new layers copied
from the last inherited one (a planted fault of the expansion).  One JSON
line per seed on standard output.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--modes", default="program,bf16,half_batch")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import harness
    import jax
    from repro.launch import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.load_cell(args.workload)
    harness.check_devices(cell.chips)
    from kinds import train
    modes = args.modes.split(",")
    for seed in (int(s) for s in args.seeds.split(",")):
        out = train.readings(cell, seed, args.seconds, modes)
        print(json.dumps({"workload": args.workload, "seed": seed, **out}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
