"""The whole command, rehearsed on the CPU at a tiny size.

Run with ``JAX_PLATFORMS=cpu python -m pytest bench/tests`` from the
checkout's root. Every rehearsal must reach a decision on correctness:
a sound program reads ``correct: true`` whatever its window caught, the
lower-precision control and each planted fault read ``false``.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

import steer

SEED = 2147483917           # above 2**31: seeds need more than 32 signed bits


def _over(line):
    return {k: c for k, c in line["checks"].items()
            if c["limit"] is None or not c["value"] <= c["limit"]}


def _checks_last(line, err):
    """The checks come last in the result line and on stderr."""
    assert list(line)[-1] == "checks"
    tail = err.strip().splitlines()[-len(line["checks"]):]
    assert [t.split()[1] for t in tail] == list(line["checks"])


@pytest.mark.parametrize("where", ["checkout_without_chip",
                                   "benchmark_alone"])
def test_refuses(where, tmp_path):
    root = steer.ROOT
    if where == "benchmark_alone":
        shutil.copy(root / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
        shutil.copytree(steer.BENCH, tmp_path / "bench",
                        ignore=shutil.ignore_patterns("__pycache__"))
        root = tmp_path
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "metrpo-arm7.paced64",
         "--seed", "1", "--seconds", "1"], cwd=root, env=env,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout == ""


@pytest.mark.parametrize("cell,trace", [
    ("metrpo-arm7.unpaced64", 0), ("metrpo-arm7.unpaced64", 1),
    ("meppo-arm7.paced64", 0)])
def test_sound_run_is_correct(cell, trace, monkeypatch, tmp_path):
    run = steer.steer(monkeypatch, tmp_path)
    rc, line, err = steer.rehearse(run, "--workload", cell, "--seed",
                                   str(SEED), "--seconds", "3", "--trace",
                                   str(trace))
    assert rc == 0, err[-3000:]
    assert line["correct"] is True, _over(line)
    assert line["failed"] == 0 and line["attempted"] == len(line["checks"])
    assert line["device"]["platform"] == "cpu"
    assert "window_ring" in line["checks"]
    _checks_last(line, err)
    if trace:
        assert "busy_s" in line["device"] and "window_s" in line["device"]
    else:
        assert "setup_s" in line["metrics"]
        assert "policy_steps_per_s" in line["metrics"]


def test_window_shorter_than_a_landing_decides(monkeypatch, tmp_path):
    """The refused run of the first benchmark: a traced paced window
    shorter than one landing, so the model learner adds no version. The
    check decides, and the sound program reads correct."""
    run = steer.steer(monkeypatch, tmp_path)
    from harness import runner
    versions = {}
    window = runner.run_window

    def spy(tr, traffic, seconds, ev, **kw):
        before = tr.model_server.version
        w = window(tr, traffic, seconds, ev, **kw)
        versions["added"] = tr.model_server.version - before
        return w
    monkeypatch.setattr(runner, "run_window", spy)
    instrument = runner.instrument

    def stopped(tr, ev, config):    # the model learner has early-stopped
        tr.model_worker.stopper.stopped = True
        instrument(tr, ev, config)
    monkeypatch.setattr(runner, "instrument", stopped)
    # a landing is 10 s of robot time; the window lasts 0.5 s, and no
    # collector claims a batch in it
    from repro.core.servers import DataServer
    monkeypatch.setattr(DataServer, "try_claim", lambda self, *a, **k: 0)
    rc, line, err = steer.rehearse(run, "--workload", "metrpo-arm7.paced64",
                                   "--seed", str(SEED), "--seconds", "0.5",
                                   "--trace", "1")
    assert rc == 0, err[-3000:]
    assert versions["added"] == 0
    assert json.loads(err.split("[window] ")[1].splitlines()[0])[
        "trajs_landed"] == 0
    assert line["correct"] is True, _over(line)
    _checks_last(line, err)
