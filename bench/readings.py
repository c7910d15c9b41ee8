#!/usr/bin/env python3
"""Readings that set the correctness limits of a configuration.

    python3 bench/readings.py --workload metrpo-arm7.paced64 \\
        --seeds 12 --first-seed 3000000001 --control-seeds 3

On the chip, in one process: for each seed the program's set-up (the
very steps the benchmark's check compares, ``harness/runner.setup``)
against the reference; for the first ``--control-seeds`` seeds also the
lower-precision control (the reference in bf16x3 put in the program's
place) and the half-batch fault (planted in the reference put in the
program's place). One JSON line per reading on stdout:
``{"seed", "kind": "program"|"control"|"half_batch", "numbers"}``.
The limits in ``configs/<name>.json`` were set from these readings, as
``PERF.md`` records; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    for p in (str(ROOT / "src"), str(BENCH)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import run
    from harness import cells, check, reference, runner
    cell = cells.load(args.workload)
    run.configure_jax(cell.config)
    run.check_device(cell.chips)

    def emit(seed, kind, numbers, t0):
        print(json.dumps({"seed": seed, "kind": kind, "numbers": numbers,
                          "seconds": time.perf_counter() - t0}), flush=True)

    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.perf_counter()
        tr = runner.build(cell.config, cell.traffic, seed)
        prog = runner.setup(tr, cell.config, cell.traffic)
        del tr
        gc.collect()
        ref = reference.run_setup(cell.config, cell.traffic, seed,
                                  prog["rounds"])
        emit(seed, "program", check.numbers(prog, ref, cell.config), t0)
        if i < args.control_seeds:
            for kind, kw in (("control", {"mm": reference.Matmul(True)}),
                             ("half_batch", {"fault": "half_batch"})):
                t0 = time.perf_counter()
                other = reference.run_setup(cell.config, cell.traffic, seed,
                                            prog["rounds"], **kw)
                emit(seed, kind, check.numbers(other, ref, cell.config), t0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
