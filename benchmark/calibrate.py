#!/usr/bin/env python3
"""Reads, on the chip, the numbers a cell's limits are set from.

    python3 benchmark/calibrate.py --workload <cell> --seeds 1,2,... \\
        [--control-seeds 1,2,3] [--fault-seeds 1,2,3] [--seconds 3]

One process, many seeds (set-up is long, so the program's dozen seeds and
the control's are read together). For every seed it runs the cell's driver
as ``run.py`` does (set-up, a short window at the cell's own load,
release) and prints what ``check`` would compare: the lower reading of
each number is the largest of these. For the control seeds it puts the
plain reference, computed in ``--quant`` (the precision below the
configuration's), in the program's place; for the fault seeds, the
reference with each of the driver's ``FAULTS`` planted in it. The
upper reading of a number is the smallest that the control (or a fault
that reads far enough above the lower reading) gives. The benchmark's own
runs never call this; PERF.md records the readings and the limits chosen.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def ints(text: str) -> list:
    return [int(t) for t in text.split(",") if t]


def main(argv: list | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=ints, required=True)
    ap.add_argument("--control-seeds", type=ints, default=[])
    ap.add_argument("--fault-seeds", type=ints, default=[])
    ap.add_argument("--quant", default="float8_e4m3fn")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    sys.path.insert(0, HERE)
    import run as bench

    _, cell, config, workload = bench.load_cell(args.workload)
    from mmlspark_tpu.utils.jit_cache import place_compilation_cache
    place_compilation_cache()
    _, peaks = bench.check_device(cell["chips"])
    driver = bench.load_file_module("drivers", workload["driver"])
    faults = driver.FAULTS

    for seed in args.seeds:
        ctx = bench.Context(cell, config, workload, peaks, seed,
                            args.seconds, False)
        state = driver.setup(ctx)
        window = driver.measure(ctx, state)
        program = driver.program_readings(state)
        driver.release(state)
        reference = driver.reference_readings(ctx, state)
        row = {"seed": seed, "attempted": window["attempted"],
               "program": driver.compare(program, reference)}
        if seed in args.control_seeds:
            control = driver.reference_readings(ctx, state, quant=args.quant)
            row["control"] = driver.compare(control, reference)
        if seed in args.fault_seeds:
            for fault in faults:
                broken = driver.reference_readings(ctx, state, fault=fault)
                row[fault] = driver.compare(broken, reference)
        print("CALIBRATE " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
