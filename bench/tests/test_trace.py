"""The reduction from a trace to per-layer metrics, on a small trace
recorded on one v5e chip (``data/small.xplane.pb.gz``: a 0.35 s traced
window of ``metrpo-arm7.unpaced64``, seed 2147483011, with the
benchmark's spans).

The expected numbers were read from this trace when it was recorded; a
change to the reduction that moves them must say why.
"""
from __future__ import annotations

import gzip
import json
import shutil
from pathlib import Path

import pytest

import steer
from harness import cells, layers, xplane

DATA = Path(__file__).resolve().parent / "data"
TRACE = DATA / "small.xplane.pb.gz"
EXPECTED = json.loads((DATA / "small.expected.json").read_text())


@pytest.fixture(scope="module")
def ctx(tmp_path_factory):
    raw = tmp_path_factory.mktemp("trace") / "small.xplane.pb"
    with gzip.open(TRACE) as src, open(raw, "wb") as dst:
        shutil.copyfileobj(src, dst)
    cell = cells.load("metrpo-arm7.unpaced64")
    trace = xplane.load(raw)
    return layers.Context(cell, None, {"kind": "TPU v5 lite"}, trace=trace)


def test_trace_has_window_spans_and_device(ctx):
    assert 0 < ctx.window_s == pytest.approx(EXPECTED["window_s"], rel=1e-12)
    assert 0 < ctx.busy_s <= ctx.window_s
    assert ctx.busy_s == pytest.approx(EXPECTED["busy_s"], rel=1e-12)
    assert {n for n in layers.SPANS if ctx.trace.host_events(n)} == set(
        EXPECTED["spans"])


def test_programs_and_kernels_are_found(ctx):
    for program, n in EXPECTED["executions"].items():
        assert len(ctx.executions(program)) == n, program
    assert len(ctx.kernels(("jit__improve_impl",))) == EXPECTED["imag_calls"]
    assert len(ctx.kernels(("jit__train_epoch", "jit__val_loss"))) == \
        EXPECTED["gmm_calls"]


def test_metrics_read_as_recorded(ctx):
    for m in cells.load("metrpo-arm7.unpaced64").per_layer:
        got = cells.metric_reader(m["name"])(ctx)
        want = EXPECTED["metrics"].get(m["name"])
        if want is None:
            assert got is None, m["name"]
        else:
            assert got == pytest.approx(want, rel=1e-9), m["name"]
            if m["unit"] == "%":
                assert 0 < got <= 100, m["name"]


def test_breakdown_is_bounded(ctx):
    b = ctx.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert all(s > 0 for _, s in b["device_ops"] + b["idle_gaps"])
    assert sum(s for _, s in b["idle_gaps"]) <= ctx.window_s - ctx.busy_s \
        + 1e-9


def test_interval_arithmetic():
    assert xplane.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert xplane.covered([(0, 2), (1, 3), (5, 6)]) == 4
    assert xplane.gaps([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]
    assert xplane.clip([(0, 2), (4, 9)], 1, 5) == [(1, 2), (4, 5)]


assert steer  # puts the program and the benchmark on the path
