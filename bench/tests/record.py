#!/usr/bin/env python3
"""Record a short traced window on the chip, for ``test_span_metrics.py``.

    python3 bench/tests/record.py --workload metrpo-arm7.unpaced64 \\
        --seed 2147483123 --seconds 12 --trace-seconds 0.4 \\
        --out bench/tests/data/spans

Set-up and window as ``run.py`` runs them. The profiler records
``--trace-seconds`` only (a 4 s trace is too large to keep with the
tests), from the first landing after the window's middle, so that the
trace holds a drain into the ring and the epochs after it. The trace is
kept without its ``/host:metadata`` plane (the programs' HLO, half its
size), which no reader reads. Writes ``<out>.xplane.pb.gz`` and
``<out>.expected.json``: what the readers read from the trace when it was
recorded.
"""
from __future__ import annotations

import argparse
import gc
import gzip
import json
import sys
import tempfile
import threading
import time
from pathlib import Path

import steer


def _varint(buf: bytes, i: int):
    n = shift = 0
    while True:
        b = buf[i]
        i += 1
        n |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return n, i


def _fields(buf: bytes):
    """(field number, the field's bytes, its payload if length-delimited)
    of each top-level field of a protobuf message, in order."""
    i = 0
    while i < len(buf):
        start = i
        key, i = _varint(buf, i)
        wire, payload = key & 7, None
        if wire == 0:
            _, i = _varint(buf, i)
        elif wire == 1:
            i += 8
        elif wire == 2:
            n, i = _varint(buf, i)
            payload, i = buf[i:i + n], i + n
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"unexpected protobuf wire type {wire}")
        yield key >> 3, buf[start:i], payload


def drop_plane(xspace: bytes, name: str) -> bytes:
    """The serialized ``XSpace`` without its plane ``name`` (an XSpace's
    planes are its field 1, a plane's name its field 2)."""
    mark = name.encode()
    out = []
    for num, whole, payload in _fields(xspace):
        if num == 1 and any(n == 2 and p == mark
                            for n, _, p in _fields(payload)):
            continue
        out.append(whole)
    return b"".join(out)


def trace_after_landing(ev, trace_dir, after_s: float, seconds: float):
    """A thread that waits ``after_s``, then for the next landing, and
    traces ``seconds`` from there under a span named ``traced``."""
    import jax

    def trace():
        time.sleep(after_s)
        n = len(ev.landed)
        while len(ev.landed) == n:
            time.sleep(0.001)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        with jax.profiler.TraceAnnotation("traced"):
            time.sleep(seconds)
        jax.profiler.stop_trace()
    th = threading.Thread(target=trace, name="recorder", daemon=True)
    th.start()
    return th


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace-seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    run = steer.load_run()
    from harness import cells, layers, runner, spans, xplane
    cell = cells.load(args.workload)
    run.configure_jax(cell.config)
    run.check_device(cell.chips)
    tr = runner.build(cell.config, cell.traffic, args.seed)
    runner.setup(tr, cell.config, cell.traffic)
    if not cell.traffic["paced"]:
        runner.warm_ingest(tr, cell.traffic)
    ev = runner.Events()
    runner.instrument(tr, ev, cell.config)
    gc.collect()
    with tempfile.TemporaryDirectory() as tmp:
        trace_dir = Path(tmp) / "trace"
        th = trace_after_landing(ev, trace_dir, args.seconds / 2,
                                 args.trace_seconds)
        runner.run_window(tr, cell.traffic, args.seconds, ev)
        th.join(args.seconds)
        if th.is_alive():
            raise RuntimeError("no landing to trace from in the window's "
                               "second half")
        path = xplane.find(trace_dir)
        ctx = layers.Context(cell, trace_dir,
                             runner.device_info(cell.chips))
        lines = spans.thread_lines(path)
        out = Path(args.out)
        with gzip.open(f"{out}.xplane.pb.gz", "wb") as dst:
            dst.write(drop_plane(path.read_bytes(), "/host:metadata"))
    expected = {
        "workload": args.workload, "seed": args.seed,
        "window_s": ctx.window_s, "busy_s": ctx.busy_s,
        "metrics": {m["name"]: cells.metric_reader(m["name"])(ctx)
                    for m in cell.per_layer},
        "clock_pairs": len(spans.clock_order(ctx.trace)),
        "held": {r: sorted({e.name for e in ln})
                 for r, ln in spans.roles(lines).items()},
        "idle": spans.idle_under(ctx, lines),
    }
    Path(f"{out}.expected.json").write_text(json.dumps(expected, indent=1))
    print(json.dumps(expected), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
