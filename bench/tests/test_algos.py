"""An algorithm is a file of ``bench/algos/``, found by the configuration's
``algo``; the harness names no algorithm itself.

* the reference's set-up at the rehearsal's tiny size, for both
  configurations, sound, under the bf16x3 control and under the
  half-batch fault, and the check's numbers of the latter two against
  the former, are bit for bit the record in ``data/algos.expected.json``
  (re-record with ``python bench/tests/test_algos.py`` only for a change
  meant to move the reference);
* each algorithm's operation count of a policy step at the cells' sizes;
* a new algorithm is a new file: a copy of ``me_ppo.py`` under another
  name, in a directory the loader is pointed at, runs the reference's
  set-up and the check with no other file changed;
* an unknown ``algo`` stops ``run.py`` before anything runs;
* the trainer's ``AlgoConfig`` takes every field the configuration names.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

import steer

SEED = 2147483917
RECORD = steer.BENCH / "tests" / "data" / "algos.expected.json"
CELLS = ("metrpo-arm7.paced64", "meppo-arm7.paced64")
KINDS = ("sound", "control", "half_batch")


def _tiny(cell):
    from harness import cells
    return steer.shrink(cells.load(cell))


def _rounds(c) -> int:
    from harness import runner
    return runner.fill_rounds(c.config, c.traffic["robots_per_collector"]
                              * c.traffic["collectors"])


def _setup(c, kind):
    from harness import reference
    kw = {"sound": {}, "control": {"mm": reference.Matmul(bf16x3=True)},
          "half_batch": {"fault": "half_batch"}}[kind]
    return reference.run_setup(c.config, c.traffic, SEED, _rounds(c), **kw)


def digests(out) -> dict:
    """{leaf path: dtype, shape and sha256 of its bytes}."""
    flat, _ = jax.tree_util.tree_flatten_with_path(out)
    return {jax.tree_util.keystr(p): f"{x.dtype}{list(x.shape)}:"
            + hashlib.sha256(np.ascontiguousarray(x).tobytes()).hexdigest()
            for p, x in flat}


def record_of(cell) -> dict:
    from harness import check
    c = _tiny(cell)
    outs = {k: _setup(c, k) for k in KINDS}
    return {"setup": {k: digests(v) for k, v in outs.items()},
            "numbers": {k: check.numbers(outs[k], outs["sound"], c.config)
                        for k in KINDS[1:]}}


@pytest.mark.parametrize("cell", CELLS)
def test_reference_matches_record(cell):
    want = json.loads(RECORD.read_text())[cell]
    got = json.loads(json.dumps(record_of(cell)))
    for kind in KINDS:
        assert got["setup"][kind] == want["setup"][kind], kind
    assert got["numbers"] == want["numbers"]


@pytest.mark.parametrize("name,ops", [("metrpo-arm7", 146_045_665_280),
                                      ("meppo-arm7", 104_647_884_800)])
def test_step_ops(name, ops):
    from harness import cells, flops
    config = json.loads((steer.BENCH / "configs" / f"{name}.json")
                        .read_text())
    assert cells.algorithm(config["algo"]).step_ops(config) == ops
    assert flops.policy_step(config) == ops


def test_new_algorithm_is_a_file(monkeypatch, tmp_path):
    from harness import cells, check, flops, reference
    c = _tiny("meppo-arm7.paced64")
    ops = flops.policy_step(c.config)
    shutil.copy(cells.ALGOS / "me_ppo.py", tmp_path / "ppo_copy.py")
    monkeypatch.setattr(cells, "ALGOS", tmp_path)
    config = {**c.config, "algo": "ppo-copy"}
    out = reference.run_setup(config, c.traffic, SEED, _rounds(c))
    assert digests(out) == json.loads(RECORD.read_text())[
        "meppo-arm7.paced64"]["setup"]["sound"]
    numbers = check.numbers(out, out, config)
    assert numbers["policy_step"] == 0.0 and numbers["policy_change"] == 0.0
    assert flops.policy_step(config) == ops
    with pytest.raises(SystemExit, match="ppo-copy"):
        cells.algorithm("me-ppo")       # only the pointed-at directory


def test_unknown_algorithm_runs_nothing(tmp_path):
    shutil.copy(steer.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(steer.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(steer.ROOT / "src", tmp_path / "src")
    cfg = tmp_path / "bench" / "configs" / "metrpo-arm7.json"
    cfg.write_text(json.dumps({**json.loads(cfg.read_text()),
                               "algo": "no-such-algo"}))
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "metrpo-arm7.paced64",
         "--seed", "1", "--seconds", "1"], cwd=tmp_path,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "no-such-algo" in proc.stderr
    assert "me-ppo" in proc.stderr and "me-trpo" in proc.stderr
    assert "JAX found" not in proc.stderr       # stopped before the device


def test_algo_config_takes_every_named_field():
    from harness import runner
    from repro.mbrl import AlgoConfig
    for name in ("metrpo-arm7", "meppo-arm7"):
        c = json.loads((steer.BENCH / "configs" / f"{name}.json")
                       .read_text())
        assert runner.algo_config(c) == AlgoConfig(
            algo=c["algo"], imagine_batch=c["imagine_batch"],
            imagine_horizon=c["imagine_horizon"], gamma=c["gamma"],
            max_kl=c["max_kl"], ppo_lr=c["ppo_lr"],
            n_models=c["n_models"])
        assert runner.algo_config(c).inner_lr == AlgoConfig().inner_lr
    got = runner.algo_config({**c, "algo": "mb-mpo", "inner_lr": 0.1})
    assert got.algo == "mb-mpo" and got.inner_lr == 0.1
    assert dataclasses.replace(got, algo=c["algo"], inner_lr=AlgoConfig(
    ).inner_lr) == runner.algo_config(c)
    with pytest.raises(KeyError, match="algo"):
        runner.algo_config({k: v for k, v in c.items() if k != "algo"})


if __name__ == "__main__":
    RECORD.write_text(json.dumps({cell: record_of(cell) for cell in CELLS},
                                 indent=1) + "\n")
