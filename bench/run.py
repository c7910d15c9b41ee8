#!/usr/bin/env python3
"""Chip benchmark of the asynchronous model-based RL trainer.

    python3 bench/run.py --workload metrpo-arm7.paced64 --seed 7 \\
        --seconds 40 --trace 0

Runs one cell of ``BENCHMARK.json`` on the chip this process holds:

1. device — JAX must find a TPU and as many chips as the cell asks for;
   otherwise the run exits non-zero and prints no result;
2. set-up — the program's ``AsyncTrainer(mode="threads")`` built from the
   cell's configuration and traffic, weights from ``--seed``, driven to a
   point fixed by counts: the ring filled by unpaced farm steps, each
   learner stepped (``harness/runner.py``). Every program the window runs
   is compiled here; ``setup_s`` is the time to this point;
3. window — the engine runs ``--seconds`` under the cell's pacing; with
   ``--trace 1`` the profiler records it;
4. correctness — the set-up steps against the plain reference
   (``harness/reference.py``, with the policy step of the configuration's
   algorithm from ``algos/<algo>.py``; ``harness/check.py``), and the
   ring the window's drains left against the reference's layout of what
   they moved, after the window, with the program's state freed.

The last line of stdout is one JSON object: ``correct``, ``attempted``
and ``failed`` (numbers compared, and over their limits), ``metrics``
(end-to-end with ``--trace 0``, per-layer with ``--trace 1``),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``:
each number compared with its limit. The checks are also the last lines
of stderr.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = ROOT / ".bench_out"       # traces, rewritten by every run
PLATFORM = "tpu"                # the only platform a run accepts


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def configure_jax(config: dict) -> None:
    import jax
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache:
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    # cache every program, however quick to compile: set-up then loads
    # all of them from the checkout's cache after the first run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if config["precision"] != "float32-highest":
        raise ValueError(f"unknown precision {config['precision']!r}")
    # the configuration states float32 at HIGHEST for every product, as
    # the kernels state it; the rest of the program takes JAX's default
    jax.config.update("jax_default_matmul_precision", "highest")


def check_device(chips: int) -> None:
    import jax
    devs = jax.devices()
    if devs[0].platform != PLATFORM or len(devs) < chips:
        log(f"needs {chips} {PLATFORM} chip(s); JAX found {len(devs)} "
            f"{devs[0].platform!r} device(s); nothing was run")
        raise SystemExit(3)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        log(f"no program next to the benchmark: {ROOT / 'src' / 'repro'} "
            f"is missing; nothing was run")
        return 2
    for p in (str(ROOT / "src"), str(BENCH)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from harness import cells
    cell = cells.load(args.workload)
    cells.algorithm(cell.config["algo"])    # an unknown one stops the run
    configure_jax(cell.config)
    check_device(cell.chips)

    import jax

    from harness import check, layers, reference, runner

    tr = runner.build(cell.config, cell.traffic, args.seed)
    prog = runner.setup(tr, cell.config, cell.traffic)
    if not cell.traffic["paced"]:
        runner.warm_ingest(tr, cell.traffic)
    ev = runner.Events()
    runner.instrument(tr, ev, cell.config)
    runner.watch_compiles(ev)
    trace_dir = None
    if args.trace:
        shutil.rmtree(OUT, ignore_errors=True)
        trace_dir = OUT / "trace"
    gc.collect()
    setup_s = time.perf_counter() - T_START
    log(f"[setup] {setup_s:.3f} s: {prog['rounds']} farm rounds, ring "
        f"{prog['ring_size']}+{prog['ring_val_size']} rows "
        f"(full={prog['ring_full']}), model v{prog['model_version_seen']}")

    w = runner.run_window(tr, cell.traffic, args.seconds, ev,
                          trace_dir=trace_dir)
    n = runner.counts(ev, w)
    log(f"[window] {json.dumps(n)}")
    prog["window"] = runner.window_ring(tr, ev, prog)
    prog["window_nonfinite"] = sum(
        not runner.finite(t) for t in jax.tree.leaves(
            [tr.policy_worker.state["policy"], tr.model_worker.params]))
    device = runner.device_info(cell.chips)

    metrics, breakdown = {}, None
    if args.trace:
        ctx = layers.Context(cell, trace_dir, device)
        for m in cell.per_layer:
            v = cells.metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"], device["window_s"] = ctx.busy_s, ctx.window_s
        breakdown = ctx.breakdown()
    else:
        values = {"setup_s": setup_s,
                  "policy_steps_per_s": runner.policy_steps_per_s(ev, w),
                  "trajs_per_s": runner.trajs_per_s(ev, w)}
        for m in cell.end_to_end:
            if values[m["name"]] is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}

    # the reference runs with the program's state freed
    del tr
    gc.collect()
    ref = reference.run_setup(cell.config, cell.traffic, args.seed,
                              prog["rounds"])
    checks = check.judge(check.numbers(prog, ref, cell.config),
                         cell.config["limits"])
    correct = check.passed(checks)
    failed = sum(not check.passed({k: c}) for k, c in checks.items())
    line = {"correct": correct, "attempted": len(checks), "failed": failed,
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    for k, c in checks.items():
        log(f"[check] {k} {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if check.passed({k: c}) else 'FAIL'}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
