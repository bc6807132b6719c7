#!/usr/bin/env python3
"""Readings that the limits of a cell's correctness check are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 11,12 \
        [--control-seeds 21,22,23] [--fault-seeds 31,32,33] [--leaves]

In one process, at the cell's own size, on the chip:

* for each of ``--seeds``, the set-up of a run (``run.set_up``, which
  drives the trainer through the rounds the reference follows) and its
  readings; training's readings need no measured window;
* for each of ``--control-seeds``, the control: the reference computed in
  bfloat16, put in the program's place, against the float32 reference;
* for each of ``--fault-seeds``, each fault of ``faults.py`` planted under
  the timed path.

Prints one JSON line per reading. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import correct  # noqa: E402
import faults  # noqa: E402
import program  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402


def seeds(text):
    return [int(s) for s in text.split(",") if s]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, default=[])
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--fault-seeds", type=seeds, default=[])
    ap.add_argument("--faults", default=None,
                    help="comma-separated; default every fault the cell "
                         "can have (no_exchange on several chips only)")
    ap.add_argument("--leaves", action="store_true",
                    help="also print each leaf's gap behind the readings")
    args = ap.parse_args(argv)

    import jax.numpy as jnp

    cell = run.load("cells", args.workload)
    config = run.load("configs", cell["config"])
    traffic = run.load("traffic", cell["traffic"])
    run.enable_compile_cache()
    run.accelerator(cell["chips"])
    counter = run.CompileCounter()

    def emit(kind, seed, values, **extra):
        print(json.dumps({"kind": kind, "seed": seed, **values, **extra}),
              flush=True)

    def readings(seed):
        """Set-up and comparison of a run: the readings need no window."""
        tr, data, first = run.set_up(config, cell, traffic, seed, counter,
                                     extra_rounds=0)
        first = run.host_copy(first)
        del tr
        gc.collect()
        ref = reference.run(config, data, seed, rounds=run.SETUP_ROUNDS)
        return compared(first, ref)

    def compared(prog, ref):
        values = correct.readings(prog, ref, sizes)
        if args.leaves:
            values["leaves"] = correct.leaf_readings(prog, ref, sizes)
        return values

    sizes = reference.leaf_sizes(config["model"])
    for seed in args.seeds:
        emit("sound", seed, readings(seed))
    for seed in args.control_seeds:
        data = program.make_data(config, traffic, seed)
        ref = reference.run(config, data, seed)
        low = reference.run(config, data, seed, dtype=jnp.bfloat16)
        emit("control", seed, compared(correct.as_program(low, sizes), ref))
    kinds = [k for k in args.faults.split(",") if k] if args.faults \
        else [k for k in faults.FAULTS
              if cell["chips"] > 1 or k != "no_exchange"]
    for kind in kinds:
        for seed in args.fault_seeds:
            with faults.planted(kind):
                values = readings(seed)
            emit(kind, seed, values)


if __name__ == "__main__":
    main()
