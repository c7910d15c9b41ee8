"""Steady-state hot-path benchmark + regression gate.

Measures what the async engine's retrace-free, zero-copy plumbing is
supposed to guarantee (and what the seed code violated):

* per-step steady-state latency of each worker (collect one trajectory /
  one model epoch / one policy-improvement step);
* retrace counts: the ring trainer's ``train_epoch`` must compile ONCE
  across growing buffer fills (seed behavior: one XLA retrace per data
  refresh);
* parameter-server costs: ``pull_if_newer`` on an unchanged version
  (lock + int compare) vs a full ``pull_host`` materialisation;
* end-to-end ``threads``-mode throughput (trajs/s, policy steps/s);
* end-to-end ``procs``-mode throughput (separate OS processes over
  shared-memory stores; ``procs_policy_steps_per_s`` is the post-warmup
  steady-state rate, directly comparable to the threads metric);
* with ``--collect-scaling``: collector-fleet scaling (ISSUE 5) —
  paced trajs/s at N=1,2,4 in threads and procs modes, and the
  event-mode Fig. 4 regeneration (fewer policy steps to the global
  criterion at N>1). Rates/counts only: never gated.
* with ``--env-farm``: vectorized env-farm scaling (ISSUE 6) — paced
  trajs/s at B=1,64,256 envs per collector (threads N=1,2 and procs),
  plus the raw unpaced batch-rollout rate. Rates only: never gated.
* with ``--serve``: serving-tier latency/throughput (ISSUE 8) —
  continuous-batching tokens/s, p50/p95 per-token latency, hot-swap
  stall and the compile-count invariants (serve_* metrics, never
  gated; the compile counts are exact-banded by tools/bench_drift.py).
* with ``--transport``: the PR 9 transport seam — shm vs tcp parameter
  push / changed pull / unchanged-pull-x100 latencies and a tcp data
  round-trip (transport_*_usec metrics, never gated), plus the hard
  zero-array-bytes-on-unchanged-tcp-pull invariant.
* with ``--imagine-fused``: the ISSUE 10 fused-imagination receipt —
  the same rollout timed back-to-back through the legacy two-call scan
  step and the fused ``step_fused`` dispatcher (parity ``_require``d,
  speedup floor 1.15x hard-required; ``imagine_fused_speedup_x`` is
  exact-floored by tools/bench_drift.py).

Run without flags to (re-)write the ``BENCH_hotpath.json`` baseline at
the repo root. With ``--check``, compares fresh numbers against the
committed baseline WITHOUT rewriting it and FAILS (exit 1) on a >20%
latency regression, so the perf trajectory is tracked PR over PR:

  python -m benchmarks.hotpath --check        # or: make bench-hotpath
  python -m benchmarks.hotpath                # re-baseline deliberately

The latencies are absolute wall-clock on the measuring host: the gate is
meaningful on the machine class that produced the baseline. On different
hardware, re-baseline first.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parent.parent
BASELINE = REPO_ROOT / "BENCH_hotpath.json"
REGRESSION_TOL = 0.20          # fail --check beyond +20% on any _us metric
JITTER_FLOOR_US = 150.0        # minimum absolute slack: for sub-ms
                               # metrics 20% is below scheduler jitter;
                               # any real regression on those paths
                               # (e.g. reintroducing a host copy) is
                               # orders of magnitude, so it still trips
WARMUP = 3
REPS = 20
MICRO_REPS = 100               # sub-ms metrics: min over a longer window
                               # so one background burst can't poison it


def _require_cpu_host():
    """This is a CPU-host tool: its procs and sharded phases spawn child
    processes, and a child cannot reach a chip this process holds."""
    backend = jax.default_backend()
    if backend != "cpu":
        raise SystemExit(
            f"benchmarks.hotpath measures the CPU host and needs "
            f"JAX_PLATFORMS=cpu; found backend {backend!r}")


def _require(ok, msg):
    """assert that survives python -O: the timed closures' work must not
    silently vanish (stripped asserts would time empty functions)."""
    if not ok:
        raise RuntimeError(msg)


def _timeit(fn, reps=REPS, warmup=WARMUP):
    """Best-case wall latency of fn() in microseconds (block on result).
    Min over reps: the noise-robust estimator for steady-state latency on
    a shared machine — medians swing with background load and would trip
    the 20% regression gate spuriously."""
    for _ in range(warmup):
        fn()
    samples = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        samples.append((time.perf_counter() - t0) * 1e6)
    return round(min(samples), 1)


def _block(x):
    for leaf in jax.tree.leaves(x):
        if hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()
    return x


def _build(env_name="pendulum", algo_name="me-trpo"):
    from benchmarks.common import build_algo

    from repro.core import RunConfig
    from repro.envs import make_env
    env = make_env(env_name)
    ens, pol, acfg, algo = build_algo(env, algo_name)
    rc = RunConfig(total_trajs=8, seed=0)
    return env, ens, algo, rc, (pol, acfg)


def bench_worker_steps(metrics):
    """Steady-state per-step latency + retrace counts for all 3 workers."""
    from repro.core import AsyncTrainer
    env, ens, algo, rc, _cfgs = _build()
    tr = AsyncTrainer(env, ens, algo, rc)

    # -- collect: steady-state gated-pull + rollout + zero-copy push
    def one_collect():
        tr.collector.step()
        _block(tr.data_server.drain())
    metrics["collect_step_us"] = _timeit(one_collect, reps=MICRO_REPS)

    # -- model: warm up, then keep feeding data so the buffer keeps
    # growing across epochs — the compile count must stay flat (the seed
    # retraced on every one of these refreshes).
    mw = tr.model_worker
    for _ in range(rc.min_warmup_trajs):
        tr.collector.step()
    mw.step()                         # builds trainer, first compile
    compiles_at_warmup = mw._train_epoch.trace_count
    for _ in range(6):                # growth phase (untimed)
        tr.collector.step()
        mw.stopper.reset()
        _require(mw.step() is not None, "model worker idled mid-growth")
    metrics["train_epoch_compiles_after_warmup"] = \
        mw._train_epoch.trace_count - compiles_at_warmup
    metrics["train_epoch_compiles_total"] = mw._train_epoch.trace_count

    # steady-state epoch latency: no new data, pure drain-check + epoch
    # (mw.step blocks via the float() on the validation loss)
    def one_epoch():
        mw.stopper.reset()
        _require(mw.step() is not None, "model worker idled in timed epoch")
    metrics["model_epoch_us"] = _timeit(one_epoch, reps=10)

    # -- policy step: model server now has params
    pw = tr.policy_worker

    def one_policy_step():
        _require(pw.step(), "policy worker had no model params")
        _block(pw.state["policy"])
    metrics["policy_step_us"] = _timeit(one_policy_step, reps=10)

    # -- imagination breakdown: the rollout alone (sample-then-compute
    # scan), so the rollout-vs-TRPO split of policy_step_us is tracked
    import jax.random as jrandom
    from repro.mbrl.algos import _rollout_with_logp
    algo_obj, rc_key = tr.policy_worker.algo, jrandom.key(0)
    model_params, _ = tr.model_server.pull()
    s0 = algo_obj.init_state_fn(rc_key, algo_obj.cfg.imagine_batch)
    pol = pw.state["policy"]
    roll = jax.jit(lambda mp, pp, s, k: _rollout_with_logp(
        mp, pp, s, k, algo_obj.cfg.imagine_horizon, algo_obj.reward_fn,
        algo_obj.predict_fn))

    def one_imagine():
        _block(roll(model_params, pol, s0, rc_key))
    metrics["imagine_rollout_us"] = _timeit(one_imagine, reps=10)
    return metrics


def bench_imagine_fused(metrics):
    """Fused-imagination speedup (ISSUE 10) — the tentpole's receipt.

    Times the SAME imagined rollout back-to-back through the legacy
    two-call scan step (``PI.sample_with_logp`` + ``predict_assigned``,
    ``fused=False``) and the fused ``DYN.step_fused`` dispatcher, at the
    headline bench sizes, after ``_require``-ing the outputs agree.
    Back-to-back on one host: the ratio is meaningful even when the
    absolute latencies aren't comparable across machines.

    ``imagine_fused_us`` / ``imagine_fused_legacy_us`` ride the 20%
    latency gate like any ``_us`` metric. ``imagine_fused_speedup_x`` is
    the gate the ratio itself answers to: a hard 1.15x floor here, and
    exact-floored drift tracking in tools/bench_drift.py (dropping below
    the committed ratio is drift; getting faster never is)."""
    import jax.numpy as jnp
    import jax.random as jrandom
    from benchmarks.common import build_algo

    from repro.envs import make_env
    from repro.mbrl import dynamics as DYN
    from repro.mbrl import policy as PI
    from repro.mbrl.algos import _rollout_with_logp

    env = make_env("pendulum")
    ens, pol_cfg, acfg, algo = build_algo(env, "me-trpo")
    key = jrandom.key(0)
    mp = DYN.init_ensemble(ens, key)
    pp = PI.init_policy(pol_cfg, key)
    s0 = env.reset_batch(key, acfg.imagine_batch)
    H, rfn = acfg.imagine_horizon, algo.reward_fn

    legacy = jax.jit(lambda m, p, s, k: _rollout_with_logp(
        m, p, s, k, H, rfn, fused=False))
    fused = jax.jit(lambda m, p, s, k: _rollout_with_logp(
        m, p, s, k, H, rfn))

    out_l = _block(legacy(mp, pp, s0, key))
    out_f = _block(fused(mp, pp, s0, key))
    for a, b in zip(out_l, out_f):
        _require(bool(jnp.allclose(a, b, atol=1e-4, rtol=1e-4)),
                 "fused rollout diverged from the legacy path")

    metrics["imagine_fused_us"] = _timeit(
        lambda: _block(fused(mp, pp, s0, key)), reps=10)
    metrics["imagine_fused_legacy_us"] = _timeit(
        lambda: _block(legacy(mp, pp, s0, key)), reps=10)
    speedup = round(metrics["imagine_fused_legacy_us"]
                    / metrics["imagine_fused_us"], 2)
    metrics["imagine_fused_speedup_x"] = speedup
    _require(speedup >= 1.15,
             f"fused imagination speedup {speedup}x below the 1.15x floor")
    return metrics


def bench_parameter_server(metrics):
    """Version-gated pull vs host materialisation."""
    import jax.numpy as jnp
    from repro.core.servers import ParameterServer
    params = {"w": [jnp.ones((256, 256)) for _ in range(4)],
              "b": [jnp.ones((256,)) for _ in range(4)]}
    ps = ParameterServer()
    ver = ps.push(params)

    def gated():
        for _ in range(100):
            v, _ = ps.pull_if_newer(ver)
            _require(v is None, "gated pull returned a value")
    metrics["pull_unchanged_x100_us"] = _timeit(gated, reps=MICRO_REPS)
    metrics["pull_host_us"] = _timeit(lambda: ps.pull_host(),
                                      reps=MICRO_REPS)
    metrics["push_us"] = _timeit(lambda: _block(ps._snapshot(params)),
                                 reps=MICRO_REPS)
    return metrics


def bench_threads_throughput(metrics):
    """End-to-end threads-mode run: real wall time, worker throughputs."""
    from repro.core import AsyncTrainer, RunConfig
    env, ens, algo, _, _cfgs = _build()
    # pace collection at 50x robot speed so the learners actually share
    # the run (unpaced, a simulated pendulum rollout takes ~1ms and the
    # stop criterion fires before the model/policy workers do anything)
    rc = RunConfig(total_trajs=16, seed=0, collect_speed=50.0,
                   pace_collection=True)
    tr = AsyncTrainer(env, ens, algo, rc, mode="threads")
    # pre-warm every compiled path (rollout, train_epoch, improve, eval)
    # so the timed run measures steady state, not first-compile
    for _ in range(rc.min_warmup_trajs):
        tr.collector.step()
    _require(tr.model_worker.step() is not None, "model warmup idled")
    _require(tr.policy_worker.step(), "policy warmup had no model")
    _block(tr.recorder._eval(tr.policy_worker.state["policy"],
                             jax.random.key(0)))
    pre_trajs = tr.collector.collected
    pre_steps = tr.policy_worker.steps
    pre_epochs = tr.model_worker.epochs
    t0 = time.perf_counter()
    tr.run()
    wall = time.perf_counter() - t0
    tr.collector.collected -= pre_trajs
    tr.policy_worker.steps -= pre_steps
    tr.model_worker.epochs -= pre_epochs
    metrics["threads_wall_s"] = round(wall, 3)
    metrics["threads_trajs_per_s"] = round(tr.collector.collected / wall, 2)
    metrics["threads_policy_steps_per_s"] = round(
        tr.policy_worker.steps / wall, 2)
    metrics["threads_model_epochs_per_s"] = round(
        tr.model_worker.epochs / wall, 2)
    return metrics


def bench_procs_throughput(metrics):
    """End-to-end procs-mode run: three spawned OS processes talking
    through shared-memory parameter stores + a trajectory queue.

    Children compile inside the run (a fresh process can't be
    pre-warmed from here), so the steady-state rates are measured over
    the POST-WARMUP window: from the first real policy improvement the
    parent observes (policy server version 2) to run end, using the
    shared version counters. ``procs_wall_s`` keeps the whole run
    including compiles for the record."""
    import threading

    from repro.core import AsyncTrainer, RunConfig
    env, ens, _algo, _, (pol, acfg) = _build()
    rc = RunConfig(total_trajs=16, seed=0, collect_speed=50.0,
                   pace_collection=True, min_warmup_trajs=4,
                   min_final_model_version=1, min_final_policy_version=40)
    tr = AsyncTrainer(env, ens, None, rc, mode="procs",
                      algo_cfg=acfg, pol_cfg=pol)
    done = {}
    th = threading.Thread(target=lambda: done.setdefault("t", tr.run()),
                          daemon=True)
    t_start = time.perf_counter()
    th.start()
    warm = None
    while th.is_alive() and warm is None:
        srv = getattr(tr, "_proc_servers", None)
        if srv and srv["policy"].version >= 2:
            warm = (time.perf_counter(), srv["policy"].version,
                    srv["model"].version)
        else:
            time.sleep(0.005)
    th.join(timeout=900)
    _require(not th.is_alive(), "procs run wedged")
    t_end = time.perf_counter()
    info = tr.proc_info
    metrics["procs_wall_s"] = round(t_end - t_start, 3)
    metrics["procs_trajs_per_s"] = round(
        info["trajs"] / (t_end - t_start), 2)
    if warm is not None:
        t_w, pv_w, mv_w = warm
        span = max(t_end - t_w, 1e-9)
        metrics["procs_policy_steps_per_s"] = round(
            (info["policy_version"] - pv_w) / span, 2)
        metrics["procs_model_epochs_per_s"] = round(
            (info["model_version"] - mv_w) / span, 2)
    else:       # run ended between polls: whole-run fallback
        metrics["procs_policy_steps_per_s"] = round(
            max(info["policy_version"] - 1, 0) / (t_end - t_start), 2)
        metrics["procs_model_epochs_per_s"] = round(
            info["model_version"] / (t_end - t_start), 2)
    return metrics


def bench_collect_scaling(metrics, *, fleet_sizes=(1, 2, 4)):
    """Collector-fleet scaling (ISSUE 5, the paper's Fig. 4 story):

    * threads + procs modes: paced (robot-rate) collection throughput in
      trajs/s at N = 1, 2, 4 — the fleet should scale it ~N× because a
      paced collector sleeps out most of each trajectory;
    * event mode: the async-vs-sync comparison regenerated at N > 1 —
      parallel collection shrinks the virtual collection span, so the
      global stopping criterion is reached in FEWER policy steps.

    All metrics are rates/counts (no ``_us`` suffix), so the >20%%
    latency gate never trips on them — they are tracked PR over PR via
    the committed baseline and the CI artifact."""
    import threading

    from repro.core import AsyncTrainer, RunConfig

    base_trajs = 12              # measured post-warmup window per run

    # -- event mode: policy steps to reach the global criterion
    for n in (1, max(fleet_sizes)):
        env, ens, algo, _, _cfgs = _build()
        tr = AsyncTrainer(env, ens, algo,
                          RunConfig(total_trajs=base_trajs, seed=0),
                          n_collectors=n)
        tr.run()
        _require(tr.data_server.total_pushed == base_trajs,
                 "event fleet criterion not exact")
        metrics[f"collect_scaling_event_n{n}_policy_steps"] = \
            tr.policy_worker.steps
        metrics[f"collect_scaling_event_n{n}_virtual_time_s"] = \
            round(tr.recorder.trace[-1]["time"], 2)

    # -- threads mode: pre-warm every compiled path (each fleet member
    # owns its rollout jit), then time a paced run
    for n in fleet_sizes:
        env, ens, algo, _, _cfgs = _build()
        rc = RunConfig(total_trajs=base_trajs, seed=0,
                       collect_speed=50.0, pace_collection=True,
                       n_collectors=n)
        tr = AsyncTrainer(env, ens, algo, rc, mode="threads")
        for w in tr.collectors:
            w.step()                    # 1 warm traj per member
        while tr.data_server.total_pushed < rc.min_warmup_trajs:
            tr.collectors[0].step()     # top up the model's warmup set
        _require(tr.model_worker.step() is not None, "model warmup idled")
        _require(tr.policy_worker.step(), "policy warmup had no model")
        _block(tr.recorder._eval(tr.policy_worker.state["policy"],
                                 jax.random.key(0)))
        # the timed window collects base_trajs MORE on top of warmup
        # (set_target counts pre-pushed trajectories)
        pre = tr.data_server.total_pushed
        tr.run_cfg.total_trajs = pre + base_trajs
        t0 = time.perf_counter()
        tr.run()
        wall = time.perf_counter() - t0
        got = tr.data_server.total_pushed - pre
        _require(got == base_trajs,
                 f"threads fleet criterion not exact ({got})")
        metrics[f"collect_scaling_threads_n{n}_trajs_per_s"] = \
            round(got / wall, 2)

    # -- procs mode: children compile in-run, so the rate is measured
    # over the post-warmup window (first N pushes seen -> last push)
    for n in fleet_sizes:
        env, ens, _algo, _, (pol, acfg) = _build()
        rc = RunConfig(total_trajs=base_trajs + n, seed=0,
                       collect_speed=50.0, pace_collection=True,
                       min_warmup_trajs=4, n_collectors=n,
                       min_final_model_version=1,
                       min_final_policy_version=1)
        tr = AsyncTrainer(env, ens, None, rc, mode="procs",
                          algo_cfg=acfg, pol_cfg=pol)
        done = {}
        th = threading.Thread(target=lambda: done.setdefault("t", tr.run()),
                              daemon=True)
        t_start = time.perf_counter()
        th.start()
        warm = None
        last = None
        seen = 0
        # the poll loop needs its OWN deadline: without one it only
        # exits when the runner thread dies, making the join timeout
        # below unreachable and hanging CI on a wedged fleet child
        while th.is_alive() and time.perf_counter() - t_start < 900:
            srv = getattr(tr, "_proc_servers", None)
            if srv:
                total = srv["data"].total_pushed
                if total > seen:
                    seen = total
                    last = time.perf_counter()
                    if warm is None and total >= n:
                        warm = (last, total)
            time.sleep(0.005)
        th.join(timeout=10)
        _require(not th.is_alive(), "collect_scaling procs run wedged")
        total = tr.proc_info["trajs"]
        _require(total == rc.total_trajs,
                 f"procs fleet criterion not exact ({total})")
        if warm is not None and last is not None and total > warm[1]:
            rate = (total - warm[1]) / max(last - warm[0], 1e-9)
        else:   # run finished between polls: whole-run fallback (incl.
            rate = total / max(time.perf_counter() - t_start, 1e-9)  # compile)
        metrics[f"collect_scaling_procs_n{n}_trajs_per_s"] = round(rate, 2)
    return metrics


def bench_env_farm(metrics, *, batch_sizes=(1, 64, 256),
                   fleet_sizes=(1, 2)):
    """Env-farm scaling (ISSUE 6): each collector simulates B envs per
    step through ONE vmapped rollout (``Env.rollout_batch``) and pushes
    the whole batch at once.

    * threads + procs modes, PACED at 50x robot speed — the same
      methodology as the headline ``threads_trajs_per_s``: a paced
      collector occupies one trajectory's robot time per step however
      many robots it simulates, so a farm of B multiplies the robot-rate
      ceiling by B as long as the batched compute fits inside the pacing
      interval. This is the paper's collection-bound regime (run time ~=
      data-collection time), where the farm is the order-of-magnitude
      lever.
    * ``env_farm_raw_b*``: the UNPACED compute-only rate of the batch
      rollout itself (one collector, learners idle) — the honest
      device-throughput gain from vmapping the scan, reported so the
      paced numbers can't be mistaken for raw compute speedup.

    All metrics are rates (no ``_us`` suffix): never gated, tracked PR
    over PR via the committed baseline and the CI artifact."""
    import threading

    from repro.core import AsyncTrainer, RunConfig, clear_eval_cache
    from repro.core.workers import clear_rollout_cache

    steps_measured = 4          # post-warmup batch steps per collector

    # -- threads mode, paced: B x N grid
    for n in fleet_sizes:
        for b in batch_sizes:
            env, ens, algo, _, _cfgs = _build()
            rc = RunConfig(total_trajs=10 ** 9, seed=0,
                           collect_speed=50.0, pace_collection=True,
                           n_collectors=n, envs_per_collector=b)
            tr = AsyncTrainer(env, ens, algo, rc, mode="threads")
            for w in tr.collectors:
                w.step()            # compiles the B-lane farm program
            while tr.data_server.total_pushed < rc.min_warmup_trajs:
                tr.collectors[0].step(1)
            # one full drain warms the burst ring-write at farm size
            _require(tr.model_worker.step() is not None,
                     "model warmup idled")
            _require(tr.policy_worker.step(), "policy warmup had no model")
            _block(tr.recorder._eval(tr.policy_worker.state["policy"],
                                     jax.random.key(0)))
            pre = tr.data_server.total_pushed
            tr.run_cfg.total_trajs = pre + steps_measured * b * n
            t0 = time.perf_counter()
            tr.run()
            wall = time.perf_counter() - t0
            got = tr.data_server.total_pushed - pre
            _require(got == steps_measured * b * n,
                     f"env-farm threads criterion not exact ({got})")
            metrics[f"env_farm_threads_n{n}_b{b}_trajs_per_s"] = \
                round(got / wall, 2)
    lo = f"env_farm_threads_n1_b{batch_sizes[0]}_trajs_per_s"
    hi = f"env_farm_threads_n1_b{max(batch_sizes)}_trajs_per_s"
    metrics[f"env_farm_threads_b{max(batch_sizes)}_speedup_x"] = \
        round(metrics[hi] / metrics[lo], 1)

    # -- raw compute: unpaced batch rollout, learners idle
    for b in batch_sizes:
        env, ens, algo, _, _cfgs = _build()
        tr = AsyncTrainer(env, ens, algo,
                          RunConfig(total_trajs=8, seed=0,
                                    envs_per_collector=b))
        w = tr.collectors[0]

        def one_batch():
            _require(w.step() is not None, "farm worker had no policy")
            _block(tr.data_server.drain())
        metrics[f"env_farm_raw_b{b}_trajs_per_s"] = round(
            b * 1e6 / _timeit(one_batch, reps=10), 2)

    # rollout programs for every (B) variant + eval programs pile up
    # across the grid above: drop them between groups (the LRU bound
    # also caps them, but the bench should not rely on eviction order)
    clear_rollout_cache()
    clear_eval_cache()

    # -- procs mode, paced: one farm collector per batch size (children
    # compile in-run; rate measured over the post-warmup window, first
    # batch seen -> last push, same protocol as collect_scaling)
    for b in batch_sizes:
        env, ens, _algo, _, (pol, acfg) = _build()
        rc = RunConfig(total_trajs=(steps_measured + 1) * b, seed=0,
                       collect_speed=50.0, pace_collection=True,
                       min_warmup_trajs=4, envs_per_collector=b,
                       min_final_model_version=1,
                       min_final_policy_version=1)
        tr = AsyncTrainer(env, ens, None, rc, mode="procs",
                          algo_cfg=acfg, pol_cfg=pol)
        done = {}
        th = threading.Thread(target=lambda: done.setdefault("t", tr.run()),
                              daemon=True)
        t_start = time.perf_counter()
        th.start()
        warm = None
        last = None
        seen = 0
        while th.is_alive() and time.perf_counter() - t_start < 900:
            srv = getattr(tr, "_proc_servers", None)
            if srv:
                total = srv["data"].total_pushed
                if total > seen:
                    seen = total
                    last = time.perf_counter()
                    if warm is None and total >= b:
                        warm = (last, total)
            time.sleep(0.005)
        th.join(timeout=10)
        _require(not th.is_alive(), "env-farm procs run wedged")
        total = tr.proc_info["trajs"]
        _require(total == rc.total_trajs,
                 f"env-farm procs criterion not exact ({total})")
        if warm is not None and last is not None and total > warm[1]:
            rate = (total - warm[1]) / max(last - warm[0], 1e-9)
        else:   # run finished between polls: whole-run fallback (incl.
            rate = total / max(time.perf_counter() - t_start, 1e-9)  # compile)
        metrics[f"env_farm_procs_b{b}_trajs_per_s"] = round(rate, 2)
    return metrics


def bench_transport(metrics):
    """Transport comparison (PR 9) — measure-only.

    The same parameter pytree pushed and pulled through each transport
    family: in-process is already covered by ``bench_parameter_server``;
    this section adds the posix-shm seqlock and the tcp control plane
    side by side, plus one trajectory claim->push->drain round-trip over
    tcp. Metric names end in ``_usec`` (not ``_us``) deliberately:
    absolute socket latencies swing with the host's network stack, so
    they ride the baseline as tracked numbers and never trip the 20%
    latency gate. The one HARD invariant — an unchanged tcp
    ``pull_if_newer`` moves ZERO array payload bytes (the version word
    rides the frame header) — is ``_require``d here and asserted again
    by tests/test_net.py."""
    import numpy as np

    from repro.core.servers import ShmParameterServer
    from repro.net import ControlPlane

    params = {"w": [np.ones((256, 256), np.float32) for _ in range(4)],
              "b": [np.ones((256,), np.float32) for _ in range(4)]}
    metrics["transport_param_payload_bytes"] = \
        sum(a.nbytes for a in jax.tree.leaves(params))

    # -- posix-shm seqlock (the procs-mode default)
    with ShmParameterServer(params) as shm:
        metrics["transport_shm_push_usec"] = \
            _timeit(lambda: shm.push(params), reps=MICRO_REPS)
        ver = shm.version

        def shm_gated():
            for _ in range(100):
                v, _ = shm.pull_if_newer(ver)
                _require(v is None, "gated shm pull returned a value")
        metrics["transport_shm_pull_unchanged_x100_usec"] = \
            _timeit(shm_gated, reps=MICRO_REPS)

        def shm_changed():
            v, _ = shm.pull_if_newer(ver - 1)   # stale: full copy-out
            _require(v is not None, "stale shm pull returned nothing")
        metrics["transport_shm_pull_changed_usec"] = \
            _timeit(shm_changed, reps=MICRO_REPS)

    # -- tcp control plane (loopback; remote adds wire RTT on top)
    with ControlPlane() as plane:
        ps = plane.parameter_server("bench", template=params)
        metrics["transport_tcp_push_usec"] = \
            _timeit(lambda: ps.push(params), reps=MICRO_REPS)
        ver = ps.version
        before = ps.array_bytes_received

        def tcp_gated():
            for _ in range(100):
                v, _ = ps.pull_if_newer(ver)
                _require(v is None, "gated tcp pull returned a value")
        metrics["transport_tcp_pull_unchanged_x100_usec"] = \
            _timeit(tcp_gated, reps=MICRO_REPS)
        metrics["transport_tcp_unchanged_payload_bytes"] = \
            ps.array_bytes_received - before
        _require(metrics["transport_tcp_unchanged_payload_bytes"] == 0,
                 "unchanged tcp pull moved array bytes over the wire")

        def tcp_changed():
            v, _ = ps.pull_if_newer(ver - 1)    # stale: full wire copy
            _require(v is not None, "stale tcp pull returned nothing")
        metrics["transport_tcp_pull_changed_usec"] = \
            _timeit(tcp_changed, reps=MICRO_REPS)

        ds = plane.data_server(n_collectors=1)
        traj = {"obs": np.ones((15, 3), np.float32),
                "act": np.ones((15, 1), np.float32),
                "rew": np.ones((15,), np.float32)}

        def data_roundtrip():
            _require(ds.try_claim(0, 1) == 1, "tcp claim denied")
            ds.push(traj, collector_id=0)
            _require(len(ds.drain()) == 1, "tcp drain lost the push")
        metrics["transport_tcp_data_roundtrip_usec"] = \
            _timeit(data_roundtrip, reps=MICRO_REPS)
        ps.close()
        ds.close()
    return metrics


def bench_serve(metrics, *, n_requests=12, max_new=16):
    """Serving-tier throughput/latency (ISSUE 8) — measure-only.

    Streams a deterministic mix of prompt lengths through the
    continuous-batching WorldModelServer with one live parameter push
    mid-run. None of these metric names end in ``_us``, so they ride
    ``tools/bench_drift.py``'s noise bands but never the 20% regression
    gate; the ``*_compiles`` counts ARE exact-banded there (a compile
    count has no noise), which pins the compile-once-under-churn
    invariant into the committed artifact.
    """
    import numpy as np
    from repro.configs import get_config
    from repro.core.servers import ParameterServer
    from repro.launch.mesh import make_smoke_mesh
    from repro.models import api as model_api
    from repro.models import lm as LM
    from repro.serve import WorldModelServer

    cfg = get_config("glm4-9b", reduced=True)
    ctx = model_api.shard_ctx(make_smoke_mesh())
    k1, k2 = jax.random.split(jax.random.key(0))
    ps = ParameterServer()
    ps.push(LM.init_params(cfg, ctx, k1))
    srv = WorldModelServer(cfg, param_server=ps, n_slots=4, max_seq=64,
                           page_len=16, prompt_buckets=(16, 32))

    rng = np.random.default_rng(0)
    # warmup: one request per bucket compiles every serve program once
    for b in srv.sched.buckets:
        srv.submit(rng.integers(0, cfg.vocab_size, b), max_new=2)
    srv.run()
    srv.sched.tick_seconds.clear()
    srv.swap_seconds.clear()

    v2 = LM.init_params(cfg, ctx, k2)
    for i in range(n_requests):
        plen = int(rng.integers(4, srv.sched.buckets[-1] + 1))
        srv.submit(rng.integers(0, cfg.vocab_size, plen), max_new=max_new)
        srv.step()
        if i == n_requests // 2:
            ps.push(v2)  # a live training push mid-run
    srv.run()

    st = srv.stats()
    _require(st["decode_compiles"] == 1, "serve decode retraced")
    _require(st["hot_swaps"] == 1, "serve hot-swap not picked up")
    _require(st["tokens_generated"] >= n_requests * max_new,
             "serve dropped tokens")
    metrics["serve_tokens_per_s"] = round(st["tokens_per_s"], 1)
    metrics["serve_p50_ms_per_token"] = round(st["p50_ms_per_token"], 3)
    metrics["serve_p95_ms_per_token"] = round(st["p95_ms_per_token"], 3)
    metrics["serve_hotswap_stall_ms"] = round(st["hotswap_stall_ms"], 3)
    metrics["serve_decode_compiles"] = st["decode_compiles"]
    metrics["serve_prefill_compiles"] = st["prefill_compiles"]
    return metrics


def bench_sharded(metrics):
    """Role-sharded hot path, measured in a SUBPROCESS forced to 8 host
    devices (the parent keeps its single device, so the single-device
    metrics above stay comparable PR over PR). Reports the same
    steady-state latencies for the (1,2,1) role split plus the cost of a
    cross-role parameter movement. New ``sharded_*_us`` metrics are
    informational until they appear in the committed baseline."""
    import os
    import subprocess
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8"
                        ).strip()
    env["PYTHONPATH"] = (str(REPO_ROOT / "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.hotpath", "--sharded-child"],
        capture_output=True, text=True, env=env, cwd=str(REPO_ROOT),
        timeout=900)
    if proc.returncode != 0:
        raise RuntimeError(
            f"sharded child failed (rc={proc.returncode}):\n"
            f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}")
    child = json.loads(proc.stdout.splitlines()[-1])
    metrics.update(child)
    return metrics


def _sharded_child() -> dict:
    """Runs INSIDE the forced-8-device subprocess: build the role-sharded
    workers and time their steady-state steps (same protocol as
    bench_worker_steps). Prints one JSON line on stdout."""
    import jax  # noqa: F811  (re-import after XLA_FLAGS took effect)
    from repro.core import AsyncTrainer
    from repro.core.roles import replicated
    from repro.core.servers import ParameterServer
    env, ens, algo, rc, _cfgs = _build()
    mesh = jax.make_mesh((8,), ("data",))
    tr = AsyncTrainer(env, ens, algo, rc, mesh=mesh, role_ratios=(1, 2, 1))
    _require(not tr.roles.shared, "8-device split must not be degenerate")
    m = {"sharded_devices": 8}

    mw = tr.model_worker
    for _ in range(rc.min_warmup_trajs):
        tr.collector.step()
    mw.step()
    compiles_at_warmup = mw._train_epoch.trace_count
    for _ in range(4):
        tr.collector.step()
        mw.stopper.reset()
        _require(mw.step() is not None, "sharded model worker idled")
    m["sharded_train_epoch_compiles_after_warmup"] = \
        mw._train_epoch.trace_count - compiles_at_warmup

    def one_epoch():
        mw.stopper.reset()
        _require(mw.step() is not None, "sharded model worker idled")
    m["sharded_model_epoch_us"] = _timeit(one_epoch, reps=10)

    pw = tr.policy_worker

    def one_policy_step():
        _require(pw.step(), "sharded policy worker had no model params")
        _block(pw.state["policy"])
    m["sharded_policy_step_us"] = _timeit(one_policy_step, reps=10)

    # cross-role movement: model-mesh params re-placed onto the policy
    # sub-mesh by a version-gated pull (device->device, no host hop).
    # One push outside the timer: a stale version re-pulls the same
    # stored value every rep, so only the device_put is measured
    ps = ParameterServer()
    src, _ = tr.model_server.pull()
    rp = replicated(tr.roles.policy)
    ver = ps.push(src)

    def cross_pull():
        val, _ = ps.pull_if_newer(ver - 1, sharding=rp)
        _require(val is not None, "stale-version pull returned nothing")
        _block(val)
    m["sharded_cross_role_pull_us"] = _timeit(cross_pull, reps=10)

    def gated():
        ver = ps.version
        for _ in range(100):
            v, _ = ps.pull_if_newer(ver, sharding=rp)
            _require(v is None, "gated sharded pull returned a value")
    m["sharded_pull_unchanged_x100_us"] = _timeit(gated, reps=MICRO_REPS)
    return m


def run_bench(*, sharded: bool = False,
              collect_scaling: bool = False,
              env_farm: bool = False,
              serve: bool = False,
              transport: bool = False,
              imagine_fused: bool = False) -> dict:
    _require_cpu_host()
    metrics = {}
    bench_worker_steps(metrics)
    bench_parameter_server(metrics)
    bench_threads_throughput(metrics)
    bench_procs_throughput(metrics)
    if imagine_fused:
        bench_imagine_fused(metrics)
    if collect_scaling:
        bench_collect_scaling(metrics)
    if env_farm:
        bench_env_farm(metrics)
    if serve:
        bench_serve(metrics)
    if transport:
        bench_transport(metrics)
    if sharded:
        bench_sharded(metrics)
    return {
        "bench": "hotpath",
        "backend": "cpu",
        "invariants": {
            "no_retrace_after_warmup":
                metrics["train_epoch_compiles_after_warmup"] == 0,
            "unchanged_pull_is_copy_free": True,   # by construction; see
            # ParameterServer.pull_if_newer and tests/test_hotpath.py
        },
        "metrics": metrics,
    }


def check_regression(fresh: dict, baseline: dict):
    """Return list of (metric, old, new, ratio) regressions >20%."""
    regressions = []
    base = baseline.get("metrics", {})
    for k, new in fresh["metrics"].items():
        if not k.endswith("_us"):
            continue
        old = base.get(k)
        if not old:
            continue
        if new > old + max(old * REGRESSION_TOL, JITTER_FLOOR_US):
            regressions.append((k, old, new, round(new / old, 2)))
    if not fresh["invariants"]["no_retrace_after_warmup"]:
        regressions.append(("train_epoch_retraced", 0,
                            fresh["metrics"]
                            ["train_epoch_compiles_after_warmup"], 0))
    return regressions


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--check", action="store_true",
                    help="fail (exit 1) on >20%% regression vs the "
                         "committed BENCH_hotpath.json before updating it")
    ap.add_argument("--sharded", action="store_true",
                    help="also measure the role-sharded path in a forced "
                         "8-device subprocess (sharded_*_us metrics)")
    ap.add_argument("--collect-scaling", action="store_true",
                    help="also measure collector-fleet scaling: trajs/s "
                         "at N=1,2,4 in threads and procs modes plus the "
                         "event-mode policy-steps-to-criterion comparison "
                         "(collect_scaling_* metrics, never gated)")
    ap.add_argument("--env-farm", action="store_true",
                    help="also measure env-farm scaling: paced trajs/s "
                         "at B=1,64,256 envs per collector in threads "
                         "(N=1,2) and procs modes, plus the raw unpaced "
                         "batch-rollout rate (env_farm_* metrics, never "
                         "gated)")
    ap.add_argument("--serve", action="store_true",
                    help="also measure the serving tier: continuous-"
                         "batching tokens/s, p50/p95 per-token latency, "
                         "hot-swap stall and compile counts (serve_* "
                         "metrics, never gated)")
    ap.add_argument("--transport", action="store_true",
                    help="also measure the transport seam: shm vs tcp "
                         "push / changed pull / unchanged-pull-x100 and "
                         "a tcp data round-trip (transport_* metrics, "
                         "never gated; the zero-bytes-on-unchanged-pull "
                         "invariant IS hard-required)")
    ap.add_argument("--imagine-fused", action="store_true",
                    help="also measure the fused-imagination speedup: "
                         "the same rollout through the legacy and fused "
                         "step back-to-back (imagine_fused_* metrics; "
                         "the 1.15x speedup floor is hard-required)")
    ap.add_argument("--sharded-child", action="store_true",
                    help=argparse.SUPPRESS)   # internal: see bench_sharded
    ap.add_argument("--out", default=str(BASELINE))
    args = ap.parse_args(argv)

    if args.sharded_child:
        print(json.dumps(_sharded_child()))
        return 0

    fresh = run_bench(sharded=args.sharded,
                      collect_scaling=args.collect_scaling,
                      env_farm=args.env_farm,
                      serve=args.serve,
                      transport=args.transport,
                      imagine_fused=args.imagine_fused)
    for k, v in fresh["metrics"].items():
        print(f"hotpath/{k},{v}")

    out = Path(args.out)
    status = 0
    if args.check and out.exists():
        baseline = json.loads(out.read_text())
        regs = check_regression(fresh, baseline)
        if regs:
            # a loaded machine can blow past 20% on the fast metrics:
            # re-measure once and keep the per-metric best before failing
            print("apparent regression; re-measuring once to rule out "
                  "background load...", file=sys.stderr)
            retry = run_bench(sharded=args.sharded)
            for k, v in retry["metrics"].items():
                old = fresh["metrics"].get(k)
                if k.endswith("_us") and isinstance(old, (int, float)):
                    fresh["metrics"][k] = min(old, v)
            fresh["invariants"]["no_retrace_after_warmup"] = (
                fresh["invariants"]["no_retrace_after_warmup"]
                and retry["invariants"]["no_retrace_after_warmup"])
            regs = check_regression(fresh, baseline)
        if regs:
            for k, old, new, ratio in regs:
                print(f"REGRESSION {k}: {old} -> {new} ({ratio}x)",
                      file=sys.stderr)
            return 1
        print(f"hotpath check ok: no metric regressed "
              f">{int(REGRESSION_TOL * 100)}% vs {out.name}")
        # --check never rewrites the baseline: a lucky quiet-machine run
        # would silently ratchet the bar down for every later run.
        # Re-baseline deliberately by running without --check.
        return status
    if out.exists():
        # re-baselining without the optional sections must not silently
        # drop their committed metrics: carry them over untouched
        skipped = [p for p, ran in (("collect_scaling_",
                                     args.collect_scaling),
                                    ("env_farm_", args.env_farm),
                                    ("serve_", args.serve),
                                    ("transport_", args.transport),
                                    ("imagine_fused_",
                                     args.imagine_fused))
                   if not ran]
        old = json.loads(out.read_text()).get("metrics", {})
        for k, v in old.items():
            if any(k.startswith(p) for p in skipped) \
                    and k not in fresh["metrics"]:
                fresh["metrics"][k] = v
    out.write_text(json.dumps(fresh, indent=1) + "\n")
    print(f"wrote {out}")
    return status


if __name__ == "__main__":
    sys.exit(main())
