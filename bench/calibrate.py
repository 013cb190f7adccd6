"""Readings that the limits of ``correct`` are set from, at a cell's own
size on its chips, in one process (the benchmark's runs never run this).

    python bench/calibrate.py --workload <name> --seeds 1,2,3 \\
        [--control-seeds 4,5,6] [--fault-seeds 7,8,9] [--out file.jsonl]

For each seed of ``--seeds``: the program's first steps against the plain
reference (the lower readings). For each of ``--control-seeds``: the
reference computed in bfloat16, put in the program's place (the control,
whose readings have to fail). For each of ``--fault-seeds``: the program
with a fault planted (half the batch left out; on several chips also the
exchange between chips left out). One JSON line per reading.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

from run import first_steps, log, tpu_devices, use_compile_cache  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import cell as cell_lib  # noqa: E402
from bench import compare, faults, program, reference  # noqa: E402


def _seeds(s: str):
    return [int(x) for x in s.split(",") if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--fault-seeds", type=_seeds, default=[])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    use_compile_cache()
    cell = cell_lib.load_cell(args.workload, with_limits=False)
    devices = tpu_devices(cell.chips)
    m, traffic = cell.config["model"], cell.traffic
    out = open(args.out, "a") if args.out else None
    refs = {}

    def ref(seed):
        if seed not in refs:
            refs[seed] = reference.run(cell.model, m, traffic, seed,
                                       cell.chips, device=devices[0])
        return refs[seed]

    def emit(kind, seed, readings):
        row = {"workload": cell.name, "kind": kind, "seed": seed,
               "numbers": compare.numbers(readings, ref(seed)),
               "losses": readings.losses, "ref_losses": ref(seed).losses}
        print(json.dumps(row), flush=True)
        if out:
            out.write(json.dumps(row) + "\n")
            out.flush()

    def program_runs(kind, seeds, fault=None):
        prog = program.build(cell.model, cell.config, traffic, devices)
        train_step = fault(prog.step) if fault else None
        step = None
        for seed in seeds:
            step, state, readings = first_steps(cell, prog, seed, step,
                                                train_step)
            del state
            gc.collect()
            emit(kind, seed, readings)

    program_runs("program", args.seeds)
    for seed in args.control_seeds:
        emit("control", seed, reference.run(
            cell.model, m, traffic, seed, cell.chips, dtype=jnp.bfloat16,
            device=devices[0]))
    if args.fault_seeds:
        program_runs("half_batch", args.fault_seeds, faults.half_batch)
        if cell.chips > 1:
            with faults.no_exchange():
                program_runs("no_exchange", args.fault_seeds)
    log("done")
    return 0


if __name__ == "__main__":
    sys.exit(main())
