"""Async framework behaviour tests (the paper's core claims, miniaturised)."""
import pathlib

import jax
import pytest

from repro.core import (AsyncTrainer, PartialAsyncDataPolicy,
                        PartialAsyncModelPolicy, RunConfig,
                        SequentialTrainer)
from repro.envs import make_env
from repro.mbrl import AlgoConfig, EnsembleConfig, PolicyConfig, make_algo


def build(env, algo="me-trpo", n_models=2):
    ens = EnsembleConfig(env.obs_dim, env.act_dim, hidden=32, n_models=n_models)
    pol = PolicyConfig(env.obs_dim, env.act_dim, hidden=16)
    acfg = AlgoConfig(algo=algo, imagine_batch=16, imagine_horizon=15,
                      n_models=n_models)
    return ens, make_algo(acfg, pol, jax.vmap(env.reward), env.reset_batch)


def test_async_faster_wallclock():
    """Fig 2: async virtual run time ~= collection time << sequential."""
    env = make_env("pendulum")
    rc = RunConfig(total_trajs=6, seed=0)
    ens, algo = build(env)
    ta = AsyncTrainer(env, ens, algo, rc).run()
    ens, algo = build(env)
    ts = SequentialTrainer(env, ens, algo, rc).run()
    t_async, t_seq = ta[-1]["time"], ts[-1]["time"]
    collection_time = 6 * env.horizon * env.dt
    assert t_async <= collection_time * 1.05, \
        "async run time must collapse to sampling time"
    assert t_seq > t_async * 1.5, (t_seq, t_async)


def test_async_takes_many_policy_steps_per_traj():
    """The async schedule gives the policy worker many steps per rollout
    (removing the grad-steps-per-iteration hyperparameter, Sec. 4).
    After the warmup dataset (min_warmup_trajs=4), the worker takes
    ~traj_time/policy_step_time = 8 steps per collected trajectory."""
    env = make_env("pendulum")
    ens, algo = build(env)
    rc = RunConfig(total_trajs=8, seed=0)
    tr = AsyncTrainer(env, ens, algo, rc)
    tr.run()
    post_warmup = tr.collector.collected - rc.min_warmup_trajs
    assert tr.policy_worker.steps > 4 * post_warmup, \
        (tr.policy_worker.steps, post_warmup)


def test_partial_async_engines_run():
    env = make_env("pendulum")
    for eng in (PartialAsyncModelPolicy, PartialAsyncDataPolicy):
        ens, algo = build(env)
        trace = eng(env, ens, algo, RunConfig(total_trajs=6, seed=0)).run()
        assert trace and trace[-1]["trajs"] >= 6


def test_virtual_clock_speed_effect():
    """Fig 5b mechanism: slower collection => more policy steps/sample."""
    env = make_env("pendulum")
    steps_per_traj = {}
    for speed in (0.5, 2.0):
        ens, algo = build(env)
        tr = AsyncTrainer(env, ens, algo,
                          RunConfig(total_trajs=5, seed=0,
                                    collect_speed=speed))
        tr.run()
        steps_per_traj[speed] = tr.policy_worker.steps / tr.collector.collected
    assert steps_per_traj[0.5] > steps_per_traj[2.0]


def test_threads_mode_smoke():
    env = make_env("pendulum")
    ens, algo = build(env)
    tr = AsyncTrainer(env, ens, algo, RunConfig(total_trajs=3, seed=0),
                      mode="threads")
    trace = tr.run()
    assert tr.collector.collected >= 3
    assert trace[-1]["trajs"] >= 3


def test_threads_trace_times_relative_and_monotonic():
    """All trace rows must be seconds since run start (mid-run records
    used to be absolute time.monotonic() while the final row was
    relative)."""
    env = make_env("pendulum")
    ens, algo = build(env)
    tr = AsyncTrainer(env, ens, algo,
                      RunConfig(total_trajs=4, seed=0,
                                eval_every_policy_steps=1),
                      mode="threads")
    trace = tr.run()
    times = [r["time"] for r in trace]
    assert all(0.0 <= t < 600.0 for t in times), times
    assert times == sorted(times), times


@pytest.mark.parametrize("worker,cfg", [
    ("model_worker", {"min_final_model_version": 1}),
    ("policy_worker", {"min_final_policy_version": 2}),
])
def test_threads_learner_failure_fails_the_run(worker, cfg):
    """A learner thread that raises must fail run(), not leave a run that
    'passes' at version 0. The version floor keeps the run waiting on
    that learner, so its error is always the one that ends it."""
    env = make_env("pendulum")
    ens, algo = build(env)
    tr = AsyncTrainer(env, ens, algo,
                      RunConfig(total_trajs=3, seed=0, **cfg),
                      mode="threads")

    def boom():
        raise ValueError("learner exploded")
    setattr(getattr(tr, worker), "step", boom)
    with pytest.raises(RuntimeError, match="learner failed") as info:
        tr.run()
    assert isinstance(info.value.__cause__, ValueError)


def test_procs_mode_refuses_tpu(monkeypatch):
    """On a chip the parent holds the device before any child spawns, so
    procs mode must refuse up front and point at threads mode."""
    env = make_env("pendulum")
    ens = EnsembleConfig(env.obs_dim, env.act_dim, hidden=32, n_models=2)
    pol = PolicyConfig(env.obs_dim, env.act_dim, hidden=16)
    acfg = AlgoConfig(algo="me-trpo", imagine_batch=16, imagine_horizon=15,
                      n_models=2)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match='mode="threads"'):
        AsyncTrainer(env, ens, None, RunConfig(total_trajs=3),
                     mode="procs", algo_cfg=acfg, pol_cfg=pol)


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_dir_choice(env_dir, monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and nothing else is set; unset, the
    cache goes to the fixed .jax_cache/ at the checkout root."""
    from repro.utils import compile_cache as CC
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    if env_dir:
        monkeypatch.setenv(CC.ENV_VAR, str(tmp_path))
        assert CC.enable_compile_cache() == tmp_path
        assert updates == []
    else:
        monkeypatch.delenv(CC.ENV_VAR, raising=False)
        root = pathlib.Path(__file__).resolve().parent.parent
        assert CC.enable_compile_cache() == root / ".jax_cache"
        assert updates == [("jax_compilation_cache_dir",
                            str(root / ".jax_cache"))]


def test_run_config_not_shared_between_trainers():
    env = make_env("pendulum")
    ens, algo = build(env)
    a = AsyncTrainer(env, ens, algo)
    a.run_cfg.total_trajs = 999
    ens, algo = build(env)
    b = AsyncTrainer(env, ens, algo)
    assert b.run_cfg.total_trajs != 999


def test_stopping_criterion_total_trajs():
    env = make_env("pendulum")
    ens, algo = build(env)
    tr = AsyncTrainer(env, ens, algo, RunConfig(total_trajs=4, seed=1))
    tr.run()
    assert tr.collector.collected == 4


def test_event_engine_bit_identical_and_speed_stable():
    """Regression harness for the paper's Fig. 5b machinery: the
    discrete-event engine is DETERMINISTIC — two runs with the same
    ``RunConfig.seed`` produce bit-identical traces — and its
    virtual-time cursor ordering stays stable (monotone trace, final
    virtual time scaling with 1/collect_speed) across collection
    speeds."""
    env = make_env("pendulum")
    final_times = {}
    for speed in (0.5, 1.0, 2.0):
        traces = []
        for _ in range(2):
            ens, algo = build(env)
            tr = AsyncTrainer(env, ens, algo,
                              RunConfig(total_trajs=4, seed=3,
                                        collect_speed=speed))
            traces.append(tr.run())
        assert traces[0] == traces[1], \
            f"event engine non-deterministic at collect_speed={speed}"
        times = [r["time"] for r in traces[0]]
        assert times == sorted(times), times
        final_times[speed] = times[-1]
    # the virtual clock is exact: collection dominates run time, so the
    # final cursor scales inversely with collection speed
    assert final_times[0.5] > final_times[1.0] > final_times[2.0], \
        final_times


def test_eval_cache_bounded_and_clearable():
    """_EVAL_CACHE shares one compiled eval across value-equal envs, is
    LRU-bounded (env variant sweeps can't grow it without bound), and is
    explicitly clearable for benchmarks."""
    from repro.core import runtime
    from repro.envs.classic import Pendulum

    runtime.clear_eval_cache()
    env = Pendulum(max_torque=1.875)        # value distinct from other tests
    fn1 = runtime._eval_fn(env, 2)
    assert runtime._eval_fn(Pendulum(max_torque=1.875), 2) is fn1, \
        "value-equal envs must share one compiled eval"
    assert runtime._eval_fn(env, 3) is not fn1
    assert len(runtime._EVAL_CACHE) == 2
    # sweep many env variants: the LRU bound holds and the most recently
    # used entry survives
    runtime._eval_fn(env, 2)                # touch -> fn1 becomes newest
    for i in range(runtime._EVAL_CACHE_MAX + 5):
        runtime._eval_fn(Pendulum(max_torque=3.0 + i), 2)
    assert len(runtime._EVAL_CACHE) == runtime._EVAL_CACHE_MAX
    assert (env, 3) not in runtime._EVAL_CACHE, "oldest entry must evict"
    runtime.clear_eval_cache()
    assert len(runtime._EVAL_CACHE) == 0


def test_eval_cache_eviction_keeps_live_recorders_working():
    """An LRU-evicted entry must strand nothing: a _Recorder built before
    the eviction keeps its own fn and still evaluates."""
    import jax
    import numpy as np

    from repro.core import runtime
    from repro.envs.classic import Pendulum

    runtime.clear_eval_cache()
    env = Pendulum(max_torque=1.9375)
    rec = runtime._Recorder(env, 2)
    for i in range(runtime._EVAL_CACHE_MAX + 1):    # evict rec's entry
        runtime._eval_fn(Pendulum(max_torque=5.0 + i), 2)
    assert (env, 2) not in runtime._EVAL_CACHE
    pol = runtime.PI.init_policy(
        runtime.PI.PolicyConfig(env.obs_dim, env.act_dim, hidden=4),
        jax.random.key(0))
    ret = rec.record(0.0, 1, pol, jax.random.key(1))    # first trace here
    assert np.isfinite(ret)
    runtime.clear_eval_cache()
