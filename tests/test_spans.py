"""The engine's spans, read back from a profiler trace.

Every thread of the threads-mode engine names what it is doing with
``jax.profiler.TraceAnnotation``: the spans land on the host lines of
the profiler's trace (one line per thread), on the clock of the device's
executions. What is proven here, on the CPU:

* every span of the engine fires in a short threads-mode run, a full
  garbage collection included;
* the policy learner's improvement and its parameter push nest inside
  its step, on the same line;
* no span takes a name that the chip benchmark's own wrappers use
  (``bench/harness/runner.py``), which would double them there;
* an unchanged-version ``pull_if_newer`` records no span;
* ``launch/train.py --profile-dir`` records a trace that holds them.
"""
import dataclasses
import gc
import glob
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro.core import AsyncTrainer, ParameterServer, RunConfig
from repro.envs import make_env
from repro.mbrl import AlgoConfig, EnsembleConfig, PolicyConfig, make_algo

SPANS = {"collector.step", "collector.pull", "collector.rollout",
         "data.push", "collector.pace",
         "model.step", "ring.ingest", "model.epoch", "model.val_wait",
         "model.idle",
         "policy.step", "policy.improve", "policy.eval", "policy.idle",
         "param.push", "param.pull", "gc"}
BENCHMARK_NAMES = {"traced", "farm_step", "ring_ingest", "model_step",
                   "policy_step"}


def _trace(log_dir, fn):
    """Run ``fn`` under the profiler (spans and device events only) and
    return the host plane's lines as lists of (name, start, end) in ns."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(log_dir), profiler_options=opts):
        fn()
    return _host_lines(log_dir)


def _host_lines(log_dir):
    path, = glob.glob(str(log_dir / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    host = next(p for p in ProfileData.from_file(path).planes
                if p.name == "/host:CPU")
    return [[(e.name, e.start_ns, e.start_ns + e.duration_ns)
             for e in line.events] for line in host.lines]


def _nested(inner, outer, line) -> bool:
    """Every ``inner`` event on the line lies within an ``outer`` one."""
    spans = [(a, b) for n, a, b in line if n == outer]
    return all(any(a <= s and t <= b for a, b in spans)
               for n, s, t in line if n == inner)


@pytest.fixture(scope="module")
def engine_lines(tmp_path_factory):
    env = dataclasses.replace(make_env("pendulum"), horizon=20)
    ens = EnsembleConfig(env.obs_dim, env.act_dim, hidden=16, n_models=2)
    pol = PolicyConfig(env.obs_dim, env.act_dim, hidden=16)
    acfg = AlgoConfig(algo="me-trpo", imagine_batch=16, imagine_horizon=5,
                      n_models=2)
    algo = make_algo(acfg, pol, jax.vmap(env.reward), env.reset_batch)
    # paced at 20x robot speed: a farm step sleeps out 50 ms
    rc = RunConfig(total_trajs=16, seed=0, envs_per_collector=4,
                   eval_every_policy_steps=1, eval_rollouts=2,
                   pace_collection=True, collect_speed=20.0,
                   min_final_model_version=2, min_final_policy_version=3)
    tr = AsyncTrainer(env, ens, algo, rc, mode="threads")
    pw = tr.policy_worker
    step = pw.step
    forced = []

    def step_with_a_full_collection():
        if not forced:
            forced.append(gc.collect(2))
        return step()
    pw.step = step_with_a_full_collection
    lines = _trace(tmp_path_factory.mktemp("engine"), tr.run)
    assert forced and tr.data_server.total_pushed == 16
    return lines


def test_every_span_fires(engine_lines):
    names = {n for line in engine_lines for n, _, _ in line}
    assert SPANS <= names, sorted(SPANS - names)


def test_policy_step_holds_improve_and_push(engine_lines):
    line, = [ln for ln in engine_lines
             if any(n == "policy.step" for n, _, _ in ln)]
    for inner in ("policy.improve", "param.push"):
        assert any(n == inner for n, _, _ in line), inner
        assert _nested(inner, "policy.step", line), inner
    # the forced collection ran on the policy thread, outside its step
    assert any(n == "gc" for n, _, _ in line)


def test_each_role_keeps_to_its_thread(engine_lines):
    roles = {"collector.step": "collector.", "model.step": "model.",
             "policy.step": "policy."}
    for step, prefix in roles.items():
        line, = [ln for ln in engine_lines
                 if any(n == step for n, _, _ in ln)]
        others = {n for n, _, _ in line
                  if "." in n and n.split(".")[0] in
                  ("collector", "model", "policy")}
        assert all(n.startswith(prefix) for n in others), (step, others)


def test_no_span_takes_a_benchmark_name(engine_lines):
    names = {n for line in engine_lines for n, _, _ in line}
    assert not names & BENCHMARK_NAMES


def test_unchanged_pull_records_no_span(tmp_path):
    srv = ParameterServer({"w": jnp.ones(3)})
    got = []

    def pulls():
        for _ in range(50):
            got.append(srv.pull_if_newer(1)[0])     # unchanged
        got.append(srv.pull_if_newer(0)[0])         # a newer version

    lines = _trace(tmp_path, pulls)
    assert all(v is None for v in got[:-1]) and got[-1] is not None
    assert sum(n == "param.pull" for line in lines for n, _, _ in line) == 1


def test_launcher_records_the_spans(tmp_path):
    src = Path(__file__).resolve().parents[1] / "src"
    env = {**os.environ, "PYTHONPATH": str(src), "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--task", "mbrl",
         "--mode", "threads", "--trajs", "8", "--envs-per-collector", "4",
         "--n-models", "2", "--model-hidden", "16", "--policy-hidden", "16",
         "--imagine-batch", "16", "--imagine-horizon", "5",
         "--profile-dir", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    names = {n for line in _host_lines(tmp_path) for n, _, _ in line}
    assert {"collector.step", "collector.rollout", "data.push"} <= names
