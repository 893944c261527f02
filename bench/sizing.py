#!/usr/bin/env python3
"""How much device memory a training cell's full-depth step needs, by
batch (not part of a cell's run): the compiled step's
``memory_analysis()`` at each batch asked for, then the device's
``memory_stats()`` before and after one executed step at the cell's own
batch.

    python3 bench/sizing.py --workload gpt2-12l.train-fixed --batches 32,48

One JSON line per batch, then one for the executed step, on standard out.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
FIELDS = ("temp_size_in_bytes", "argument_size_in_bytes",
          "output_size_in_bytes", "alias_size_in_bytes",
          "generated_code_size_in_bytes")


class _NoData:
    def batch(self, step, shard=0, num_shards=1):
        raise RuntimeError("sizing runs no trainer loop")


def step_for(cell, batch):
    """(compiled-to-be step, its state's shardings and shapes, the batch's
    shapes) of ``cell``'s full-depth step at ``batch`` sequences."""
    import jax
    import jax.numpy as jnp
    from kinds import train
    from repro.launch import mesh as mesh_lib
    from repro.train.engine import ProgressiveTrainer
    cell = copy.deepcopy(cell)
    cell.traffic["batch"] = batch
    cfg = train.program_cfg(cell)
    trainer = ProgressiveTrainer(
        cfg, train._train_config(cell, 10, 0),
        mesh=mesh_lib.make_train_mesh(cell.traffic["mesh"]), data=_NoData(),
        eval_batches=[], async_ckpt=False, log_fn=lambda *a: None)
    p_sh, os_sh, p_struct, os_struct = trainer._state_shardings(cfg)
    step, _ = trainer._build_steps(cfg, p_sh, os_sh)
    shape = (batch, cell.traffic["seq_len"])
    b = {k: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=trainer._batch_sh[k])
         for k in ("tokens", "labels")}
    return trainer, step, (p_sh, os_sh, p_struct, os_struct), b


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--batches", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import harness
    import jax
    import jax.numpy as jnp
    from repro.launch import compile_cache
    compile_cache.enable()
    cell = harness.load_cell(args.workload)
    devices = harness.check_devices(cell.chips)
    limit = (devices[0].memory_stats() or {}).get("bytes_limit")
    for batch in (int(b) for b in args.batches.split(",")):
        _, step, (_, _, p_struct, os_struct), b = step_for(cell, batch)
        line = {"workload": args.workload, "batch": batch,
                "bytes_limit": limit}
        try:
            ma = step.lower(p_struct, os_struct, b,
                            jnp.asarray(0)).compile().memory_analysis()
            line.update({k: getattr(ma, k) for k in FIELDS})
        except Exception as e:     # the compiler refuses what does not fit
            line["error"] = str(e).splitlines()[0][:300]
        print(json.dumps(line), flush=True)

    from kinds import train
    batch = cell.traffic["batch"]
    trainer, step, (p_sh, os_sh, _, _), b = step_for(cell, batch)
    params = train.weights_fn(cell, 0, cell.model["num_layers"], p_sh)()
    state = jax.jit(trainer.opt.init, out_shardings=os_sh)(params)
    zeros = {k: jax.device_put(jnp.zeros(v.shape, v.dtype), v.sharding)
             for k, v in b.items()}
    before = dict(devices[0].memory_stats() or {})
    out = step(params, state, zeros, jnp.asarray(0))
    jax.block_until_ready(out)
    after = dict(devices[0].memory_stats() or {})
    keys = ("bytes_in_use", "peak_bytes_in_use", "largest_alloc_size",
            "bytes_limit")
    print(json.dumps({"workload": args.workload, "batch": batch,
                      "executed": True,
                      "before": {k: before.get(k) for k in keys},
                      "after": {k: after.get(k) for k in keys}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
