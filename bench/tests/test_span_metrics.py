"""The readers of the program's spans (``harness/spans.py`` and the
metrics that use it), on synthetic traces and on a short trace recorded
on one v5e chip (``data/spans.xplane.pb.gz``: 0.37 s of
``metrpo-arm7.unpaced64``, seed 2147483124, from a program with its
spans, opened as a landing returned; recorded by ``record.py``, which
also wrote what the readers read then, ``data/spans.expected.json``).
"""
from __future__ import annotations

import gzip
import json
import shutil
from pathlib import Path

import pytest

import steer
from harness import cells, layers, spans, xplane

DATA = Path(__file__).resolve().parent / "data"
CELL = "metrpo-arm7.unpaced64"
METRICS = {"farm_push_ms": "data.push", "ring_ingest_ms": "ring.ingest",
           "model_sync_wait_ms": "model.val_wait",
           "policy_host_ms": "policy.step",
           "policy_eval_wait_ms": "policy.eval",
           "param_push_ms": "param.push"}
E = xplane.Event


def _ctx(host, ops=(), modules=()):
    trace = xplane.Trace(
        {"/device:TPU:0": {"XLA Ops": list(ops),
                           "XLA Modules": list(modules)}},
        {"python": [E("traced", 1.0, 2.0)] + list(host)})
    return layers.Context(cells.load(CELL), None, {"kind": "TPU v5 lite"},
                          trace=trace)


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_reader_takes_spans_whole_inside_the_window(metric):
    span = METRICS[metric]
    other = next(s for s in METRICS.values() if s != span)
    ctx = _ctx([E(span, 0.9, 1.1), E(span, 1.2, 1.203),     # straddles,
                E(span, 1.5, 1.51), E(span, 1.95, 2.05),    # inside x2,
                E(span, 2.5, 2.6), E(other, 1.3, 1.9)])     # straddles,
    read = cells.metric_reader(metric)                      # outside
    assert read(ctx) == pytest.approx(6.5, rel=1e-9)        # (3 + 10) / 2


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_reader_reads_none_without_its_span(metric):
    read = cells.metric_reader(metric)
    assert read(_ctx([])) is None
    assert read(_ctx([E(METRICS[metric], 0.5, 1.5)])) is None


def test_new_metrics_are_declared_for_their_cells():
    spec = json.loads((steer.ROOT / "BENCHMARK.json").read_text())
    per = {m["name"]: m for m in spec["per_layer"]}
    for name in METRICS:
        assert per[name]["unit"] == "ms" and per[name]["better"] == "lower"
        assert (steer.BENCH / "metrics" / f"{name}.py").is_file()


def test_idle_is_put_down_to_the_innermost_span():
    ops = [E("op", 1.0, 1.2), E("op", 1.5, 2.0)]        # idle 1.2-1.5
    ctx = _ctx([], ops=ops)
    policy = [E("policy.step", 1.1, 1.3), E("policy.improve", 1.15, 1.25),
              E("policy.eval", 1.35, 1.5)]
    model = [E("model.step", 1.0, 1.4), E("gc", 1.25, 1.28)]
    out = spans.idle_under(ctx, [model, policy])
    assert out["idle_s"] == pytest.approx(0.3)
    p = out["threads"]["policy"]
    assert p["by_span"]["policy.improve"] == pytest.approx(0.05)
    assert p["by_span"]["policy.step"] == pytest.approx(0.05)
    assert p["by_span"]["policy.eval"] == pytest.approx(0.15)
    assert p["by_span"]["None"] == pytest.approx(0.05)
    assert p["covered"] == pytest.approx(0.25 / 0.3)
    m = out["threads"]["model"]     # its last span ends at 1.4
    assert m["by_span"]["gc"] == pytest.approx(0.03)
    assert m["by_span"]["model.step"] == pytest.approx(0.17)
    assert m["unseen_s"] == pytest.approx(0.1) and m["covered"] == 1.0
    gap, = out["long_gaps"]
    assert gap["s"] == pytest.approx(0.3) and gap["gc"]
    assert gap["under"] == {"policy": "policy.eval", "model": "model.step"}


def test_clock_pairs_count_from_the_first_wait():
    host = [E("policy.improve", 1.00, 1.01),     # before the anchor
            E("policy.eval", 1.02, 1.10),
            E("policy.improve", 1.11, 1.12), E("policy.improve", 1.13, 1.14)]
    modules = [E("jit__improve_impl(1)", 1.05, 1.06),   # before it ends
               E("jit__improve_impl(1)", 1.115, 1.2),
               E("jit__improve_impl(1)", 1.2, 1.3),
               E("jit_eval_return(2)", 1.3, 1.4)]
    ctx = _ctx(host, modules=modules)
    assert spans.clock_order(ctx.trace) == [(1.11, 1.115), (1.13, 1.2)]


# ------------------------------------------------- a trace from the chip
@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    raw = tmp_path_factory.mktemp("trace") / "spans.xplane.pb"
    with gzip.open(DATA / "spans.xplane.pb.gz") as src, \
            open(raw, "wb") as dst:
        shutil.copyfileobj(src, dst)
    ctx = layers.Context(cells.load(CELL), None, {"kind": "TPU v5 lite"},
                         trace=xplane.load(raw))
    return ctx, raw, json.loads((DATA / "spans.expected.json").read_text())


def test_recorded_metrics_read_as_recorded(recorded):
    ctx, _, want = recorded
    assert ctx.window_s == pytest.approx(want["window_s"], rel=1e-12)
    for m in cells.load(CELL).per_layer:
        got = cells.metric_reader(m["name"])(ctx)
        if want["metrics"].get(m["name"]) is None:
            assert got is None, m["name"]
        else:
            assert got == pytest.approx(want["metrics"][m["name"]],
                                        rel=1e-9), m["name"]
    # a landing's push (about 1.1 s) outlasts the short window; the trace
    # opens as one lands, so it holds the drain into the ring
    assert want["metrics"]["farm_push_ms"] is None
    assert want["metrics"]["ring_ingest_ms"] is not None
    assert sum(want["metrics"][m] is not None for m in METRICS) >= 4


def test_recorded_spans_share_the_device_clock(recorded):
    ctx, _, want = recorded
    pairs = spans.clock_order(ctx.trace)
    assert len(pairs) == want["clock_pairs"] >= 5
    assert all(span <= run for span, run in pairs)


def test_recorded_threads_hold_their_spans(recorded):
    ctx, raw, want = recorded
    lines = spans.thread_lines(raw)
    held = {r: sorted({e.name for e in ln})
            for r, ln in spans.roles(lines).items()}
    assert held == want["held"] and {"policy", "model"} <= set(held)
    out = spans.idle_under(ctx, lines)
    assert out["idle_s"] == pytest.approx(want["idle"]["idle_s"], rel=1e-9)
    for role in held:
        covered = out["threads"][role]["covered"]
        assert covered == pytest.approx(
            want["idle"]["threads"][role]["covered"], rel=1e-9)
        assert covered >= 0.95, role


assert steer  # puts the program and the benchmark on the path
