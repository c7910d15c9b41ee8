"""The three workers of Figure 1a, each a pull -> step -> push loop with
the MINIMAL unit of work (one rollout / one model epoch / one policy
gradient step). The same worker objects run either as real threads
(production) or inside the deterministic discrete-event engine
(benchmarks) — see runtime.py. Data collection is a FLEET (ISSUE 5):
any number of ``DataCollectionWorker`` instances — distinct RNG streams
(``collector_key``), pluggable per-collector exploration
(``ExplorationSchedule``), one device each on the collector sub-mesh —
push into the same multi-producer data server.

Hot-path invariants (enforced by tests/test_hotpath.py and
benchmarks/hotpath.py):

* every jitted step function compiles ONCE and never retraces as the
  replay buffer fills (static ring shapes, see servers.ReplayBuffer);
* parameter pulls are version-gated: an unchanged version costs one lock
  + integer compare against a device-resident cache — no host copy, no
  re-upload.

Role meshes (core/roles.py): every worker takes an optional ``mesh`` —
its sub-mesh of the pod. Params live replicated on the owning sub-mesh,
batch-like data is sharded along its leading axis, and cross-role
movement happens only through the placement-aware servers (explicit
device-to-device ``device_put`` on version change). ``mesh=None`` is the
single-device behaviour, bit-for-bit unchanged.

Process isolation (``mode="procs"``, runtime._run_procs): the same
worker objects ALSO run as separate OS processes. The module-level
``proc_worker_main(role, spec, channels)`` entrypoint is picklable
through the spawn context: it rebuilds env/algo/worker from plain
configs (``ProcSpec``) + seed + role inside the child — each child owns
its own jax backend — and talks only through the IPC servers in
``channels`` (ShmParameterServer / ProcDataServer). Cross-process pulls
return host arrays; the pull paths below re-home them onto the worker's
device exactly once per version change, so step loops stay
device-resident in every mode.

Spans: each worker names what its thread is doing with
``jax.profiler.TraceAnnotation`` (``collector.step``, ``model.epoch``,
``policy.improve``, ...; the servers add ``data.push``, ``param.push``
and ``param.pull``). They land in a profiler trace on the device's
clock; with no profiler session a span costs under a microsecond. The
list is in README.md, "Tracing a run".
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.core import roles as ROLES
from repro.core.servers import DataServer, ParameterServer, ReplayBuffer
from repro.mbrl import dynamics as DYN
from repro.mbrl import policy as PI
from repro.mbrl.early_stop import EMAEarlyStop
from repro.utils.compile_cache import enable_compile_cache
from repro.utils.jit_stats import jit_cache_size


def _to_device(tree):
    """Re-home host (np) leaves pulled across a process boundary onto
    this worker's device; jax.Array leaves pass through untouched (the
    in-process servers stay zero-copy)."""
    return jax.tree.map(
        lambda x: x if isinstance(x, jax.Array) else jnp.asarray(x), tree)


@dataclasses.dataclass
class WorkerTimes:
    """Nominal virtual durations (seconds) of each worker's step — used by
    the VirtualClock / discrete-event engine to reproduce the paper's
    real-robot timing (DESIGN.md §2)."""
    trajectory: float       # horizon * env.dt (robot time; exact)
    model_epoch: float = 1.0
    policy_step: float = 0.5


@dataclasses.dataclass(frozen=True)
class ExplorationSchedule:
    """Pluggable per-collector exploration for a fleet (ISSUE 5): each
    collector samples with its own action-noise scale — the paper's
    exploration mechanism fanned out heterogeneously, like the
    multi-robot setup of Gu et al. (2016). Scales cycle when the fleet
    is larger than the tuple; scale 1.0 is exactly the single-collector
    behaviour. Plain frozen dataclass of floats: picklable through the
    spawn boundary (``ProcSpec``)."""
    noise_scales: tuple = (1.0,)

    def scale_for(self, collector_id: int) -> float:
        return float(self.noise_scales[collector_id
                                       % len(self.noise_scales)])

    @classmethod
    def ladder(cls, n_collectors: int, lo: float = 0.75,
               hi: float = 1.5) -> "ExplorationSchedule":
        """Evenly spaced lo..hi noise ladder across the fleet; collector
        0 keeps scale 1.0 so its stream stays comparable to a lone
        collector. A two-collector fleet gets (1.0, hi): with one varied
        rung, the wider-exploring endpoint is the one worth adding."""
        if n_collectors <= 1:
            return cls((1.0,))
        k = n_collectors - 1            # varied rungs
        if k == 1:
            return cls((1.0, hi))
        rest = tuple(lo + (hi - lo) * i / (k - 1) for i in range(k))
        return cls((1.0,) + rest)


def collector_key(key, collector_id: int):
    """Per-collector RNG stream: collector 0 keeps the engine's base
    collector key UNTOUCHED (so a fleet of one is bit-identical to the
    pre-fleet engine); every other collector folds its id in."""
    return key if collector_id == 0 else jax.random.fold_in(
        key, collector_id)


def heartbeat_slot(role: str, n_collectors: int = 1) -> int:
    """Index of ``role``'s slot in the shared heartbeat array (see
    ProcChannels.heartbeat): model=0, policy=1, collector:<i>=2+i."""
    if role == "model":
        return 0
    if role == "policy":
        return 1
    cid = int(role.split(":", 1)[1]) if ":" in role else 0
    return 2 + (cid % max(int(n_collectors), 1))


def heartbeat_slots(n_collectors: int) -> int:
    """Total heartbeat slots for a run: model + policy + the fleet."""
    return 2 + max(int(n_collectors), 1)


def default_burst(n_collectors: int, envs_per_step: int = 1) -> int:
    """Drain burst capacity for a fleet of N collectors running B envs
    each: the one heuristic shared by the in-process engines and the
    procs-mode child model worker. An env farm's whole batch must fit
    one burst so its drain stays a single compiled scatter per chunk."""
    return max(8, 2 * int(n_collectors), int(envs_per_step))


# One compiled rollout program per (env value, noise scale, batch size)
# — N same-scale fleet members share a single trace/compile instead of
# paying N identical ones (envs are small frozen dataclasses, so
# value-equal envs share). BOUNDED exactly like runtime._EVAL_CACHE
# (ISSUE 6 satellite): plain dict in insertion order, pop + reinsert on
# hit = LRU touch, oldest evicted past _ROLLOUT_CACHE_MAX — bench
# sweeps over noise scales / batch sizes can no longer grow it without
# limit, and an evicted entry strands nothing (each worker holds its
# own fn, which stays valid standalone). Batch size None keys the
# single-trajectory program; an int keys the B-lane farm program.
_ROLLOUT_CACHE: Dict[Any, Callable] = {}
_ROLLOUT_CACHE_MAX = 64


def clear_rollout_cache() -> None:
    """Drop every cached compiled rollout, single and batched.
    Benchmarks call this between sweep groups."""
    _ROLLOUT_CACHE.clear()


def _rollout_cache_put(cache_key, build: Callable) -> Callable:
    fn = _ROLLOUT_CACHE.pop(cache_key, None)    # pop + reinsert = LRU
    if fn is None:
        fn = build()
    _ROLLOUT_CACHE[cache_key] = fn
    while len(_ROLLOUT_CACHE) > _ROLLOUT_CACHE_MAX:  # dicts iterate in
        del _ROLLOUT_CACHE[next(iter(_ROLLOUT_CACHE))]  # insertion order
    return fn


def _sampler_for(noise_scale: float):
    if noise_scale == 1.0:
        return PI.sample_action         # bit-identical lone-collector
    #                                     path, and no spurious * 1.0

    def sampler(p, s, k):
        return PI.sample_action_scaled(p, s, k, noise_scale)
    return sampler


def _rollout_jit(env, noise_scale: float):
    """Compiled single-trajectory rollout for (env value, noise scale).
    Per-device executables are jax's own cache, keyed on placement."""
    sampler = _sampler_for(noise_scale)
    return _rollout_cache_put(
        (env, float(noise_scale), None),
        lambda: jax.jit(lambda p, k: env.rollout(k, sampler, p)))


def _rollout_batch_jit(env, noise_scale: float, n: int):
    """Compiled B-lane farm rollout for (env value, noise scale, B) —
    one vmapped scan per batch size, compiled once and shared across
    same-shape claimers (a partial batch of g < B lanes hits the same
    cache entry as a worker whose full batch IS g, so the two produce
    identical trajectories from identical keys)."""
    sampler = _sampler_for(noise_scale)
    n = int(n)
    return _rollout_cache_put(
        (env, float(noise_scale), n),
        lambda: jax.jit(
            lambda p, k: env.rollout_batch(k, sampler, p, n)))


class DataCollectionWorker:
    """Algorithm 1. Pull policy θ -> collect a batch of trajectories ->
    push (``envs_per_step=1``, the default, collects exactly ONE — the
    pre-farm worker, bit for bit).

    The pull is version-gated: the worker keeps a device-resident policy
    cache and only swaps it when the server holds a newer version.

    Fleet-aware: ``collector_id`` selects this collector's RNG stream,
    its device within the collector sub-mesh (round-robin, see
    ``roles.collector_sharding``), and — via ``noise_scale`` — its rung
    on the fleet's exploration schedule.

    Farm-aware (ISSUE 6): ``envs_per_step=B`` makes every ``step``
    simulate B robots via one vmapped rollout (``Env.rollout_batch``,
    one compile per (env, noise, B)) and push all B trajectories as one
    stacked batch. The worker splits its key ONCE per step regardless
    of B — lane streams are derived inside the batch program
    (``envs.base.lane_keys``: lane 0 keeps the step key) — so the B=1
    stream is exactly the pre-farm stream."""

    def __init__(self, env, policy_server: ParameterServer,
                 data_server: DataServer, init_policy_params, key,
                 *, speed: float = 1.0, mesh=None, collector_id: int = 0,
                 noise_scale: float = 1.0, envs_per_step: int = 1):
        """``init_policy_params=None`` (procs mode): the collector has no
        in-process policy worker to borrow initial params from — it idles
        (``step`` returns None) until the policy process publishes
        version 1."""
        self.env = env
        self.policy_server = policy_server
        self.data_server = data_server
        self.collector_id = int(collector_id)
        self.noise_scale = float(noise_scale)
        self.envs_per_step = int(envs_per_step)
        if self.envs_per_step < 1:
            raise ValueError(f"envs_per_step must be >= 1, got "
                             f"{self.envs_per_step}")
        self._key = collector_key(key, self.collector_id)
        self._policy_cache = (None if init_policy_params is None else
                              jax.tree.map(jnp.asarray, init_policy_params))
        self._policy_ver = 0
        self.speed = speed  # >1: faster collection (Fig. 5b)
        self.collected = 0
        # each collector is a sequential control loop (one robot): it
        # runs on ONE device of the collector sub-mesh; a fleet spreads
        # round-robin across the sub-mesh's devices, pulls land there
        self._sharding = None
        if mesh is not None:
            self._sharding = ROLES.collector_sharding(mesh,
                                                      self.collector_id)
            if self._policy_cache is not None:
                self._policy_cache = jax.device_put(self._policy_cache,
                                                    self._sharding)
        # B=1 keeps the SINGLE-rollout compiled program (bit-identity
        # with the pre-farm engine); B>1 holds its own farm program so
        # cache eviction can't cost a recompile mid-run
        self._rollout = _rollout_jit(env, self.noise_scale)
        self._rollout_batch = (
            None if self.envs_per_step == 1 else
            _rollout_batch_jit(env, self.noise_scale, self.envs_per_step))

    def compile_count(self) -> int:
        """Compiled-program entries across this collector's OWN rollout
        jits (liveness/invariant telemetry for the chaos monitor): the
        single-rollout program plus — for a farm — its full-B program.
        Steady state is 1 (B=1) or at most 2 (B>1: the full batch, plus
        the single-rollout variant a final grant of g=1 may touch);
        anything above means a retrace. -1 when jax hides the caches."""
        fns = [self._rollout] + (
            [] if self._rollout_batch is None else [self._rollout_batch])
        sizes = [jit_cache_size(f) for f in fns]
        return -1 if any(s < 0 for s in sizes) else sum(sizes)

    def poll_policy(self) -> bool:
        """Refresh the policy cache (version-gated) WITHOUT collecting.
        True once a policy is available — procs-mode collectors spin on
        this during warmup so a claimed collection slot is always
        fulfilled by the following ``step``."""
        with TraceAnnotation("collector.pull"):
            fresh, self._policy_ver = self.policy_server.pull_if_newer(
                self._policy_ver, sharding=self._sharding)
            if fresh is not None:
                self._policy_cache = _to_device(fresh)
            return self._policy_cache is not None

    def step(self, n: Optional[int] = None) -> Optional[float]:
        """One batch of ``n`` trajectories (default: the worker's full
        ``envs_per_step``); returns its robot-time duration, or None
        when no policy has been published yet (procs-mode warmup).

        ``n < envs_per_step`` runs a PARTIAL batch through a smaller
        compiled variant — the engines pass the ticket grant here when
        fewer than B slots remain toward the global criterion, so the
        run lands exactly on ``total_trajs`` (at most one extra compile,
        at the very end of a run). The batch simulates n robots in
        PARALLEL, so the robot-time duration is one trajectory's
        regardless of n."""
        with TraceAnnotation("collector.step"):
            if not self.poll_policy():                  # Pull (gated)
                return None
            g = self.envs_per_step if n is None else int(n)
            with TraceAnnotation("collector.rollout"):
                # ONE key split per step whatever g is: the B=1 stream is
                # the pre-farm stream, and lanes derive inside the batch
                # program
                self._key, k = jax.random.split(self._key)
                if g == 1:
                    out = self._rollout(self._policy_cache, k)  # Step
                else:
                    fn = (self._rollout_batch if g == self.envs_per_step
                          else _rollout_batch_jit(self.env,
                                                  self.noise_scale, g))
                    out = fn(self._policy_cache, k)     # Step (farm)
            if g == 1:
                self.data_server.push(out,
                                      collector_id=self.collector_id)  # Push
            else:
                self.data_server.push_batch(
                    out, g, collector_id=self.collector_id)     # Push
            self.collected += g
            return (self.env.horizon * self.env.dt) / self.speed


class ModelLearningWorker:
    """Algorithm 2. Drain data -> one epoch on the local FIFO ring buffer
    (with EMA-validation early stopping, §5.4) -> push φ.

    Storage is a preallocated :class:`ReplayBuffer`; the trainer is built
    lazily on first data (capacity = max_trajs * horizon) and after that
    every epoch runs the same compiled program — no retrace as the buffer
    fills, no per-epoch concatenate, params/opt_state donated."""

    def __init__(self, ens_cfg: DYN.EnsembleConfig,
                 data_server: DataServer, model_server: ParameterServer,
                 key, *, max_trajs: int = 200, ema_weight: float = 0.9,
                 early_stop: bool = True, min_trajs: int = 4,
                 mesh=None, batch_axis: Optional[str] = None,
                 burst: int = 8):
        """``burst``: ring-write burst capacity — a drain of M
        trajectories (a fleet pushes many between epochs) lands in
        ceil(M/burst) compiled scatters instead of M."""
        self.cfg = ens_cfg
        self.data_server = data_server
        self.model_server = model_server
        self.max_trajs = max_trajs
        self.burst = max(int(burst), 1)
        self.buffer: Optional[ReplayBuffer] = None    # lazy: needs horizon
        self._key, k0 = jax.random.split(key)
        self.params = DYN.init_ensemble(ens_cfg, k0)
        # role sub-mesh: ensemble trains data-parallel — ring storage
        # sharded over the batch axis, params/opt_state replicated
        self._repl = self._batch_shard = None
        if mesh is not None:
            self._repl = ROLES.replicated(mesh)
            self._batch_shard = ROLES.batch_sharded(mesh, batch_axis)
            self.params = jax.device_put(self.params, self._repl)
        self._train_epoch = None
        self._val_loss = None
        self._update_norm = None
        self.opt_state = None
        self.stopper = EMAEarlyStop(weight=ema_weight, enabled=early_stop)
        self.epochs = 0
        self._have_data = False
        # the policy worker blocks on the model server, so deferring the
        # first push until a small initial dataset exists reproduces the
        # paper's 'acquire an initial dataset' phase (§5.3)
        self.min_trajs = min_trajs

    def _ensure_trainer(self, traj) -> None:
        if self.buffer is not None:
            return
        horizon = int(jax.tree.leaves(traj)[0].shape[0])
        capacity = self.max_trajs * horizon
        # ReplayBuffer rounds a sharded capacity up to the shard count
        # itself; read the final value back for the trainer's grid
        self.buffer = ReplayBuffer(capacity, sharding=self._batch_shard,
                                   burst_capacity=self.burst)
        opt, self._train_epoch, self._val_loss, self._update_norm = \
            DYN.make_ring_trainer(self.cfg, self.buffer.capacity,
                                  batch_sharding=self._batch_shard)
        self.opt_state = opt.init(self.params)
        if self._repl is not None:
            # the step counter is born on the default device; the first
            # epoch returns it replicated, which would retrace the second
            self.opt_state = jax.device_put(self.opt_state, self._repl)

    def compile_count(self) -> int:
        """Traces of the ring ``train_epoch`` (exact, via TraceCounted).
        The PR 1 invariant says this is 1 for the whole life of the
        worker once data exists — the chaos monitor asserts it DURING
        soak runs, per child incarnation."""
        return jit_cache_size(self._train_epoch)

    def _refresh_data(self) -> bool:
        new = self.data_server.drain()                  # Pull (move all)
        if new:
            with TraceAnnotation("ring.ingest"):
                self._ensure_trainer(new[0])
                self.buffer.extend(new)
                self._have_data = True
                self.stopper.reset()                    # §4: resume training
        return bool(new)

    def step(self) -> Optional[float]:
        """One epoch; returns None when idle (no data / early-stopped)."""
        with TraceAnnotation("model.step"):
            self._refresh_data()
            if (not self._have_data
                    or self.buffer.total_seen < self.min_trajs):
                return None
            if self.stopper.stopped:
                return None
            data, size = self.buffer.train_view()
            with TraceAnnotation("model.epoch"):
                self.params = {**self.params,
                               "norm": self._update_norm(data, size)}
                self._key, k = jax.random.split(self._key)
                self.params, self.opt_state, tr_loss = self._train_epoch(
                    self.params, self.opt_state, data, size, k)
            vdata, vsize = self.buffer.val_view()
            if vsize == 0:
                # no held-out traj yet: validate on a val-ring-SHAPED
                # slice of the train ring, so _val_loss still compiles
                # only once
                vcap = self.buffer.val_capacity
                vdata = {k: v[:vcap] for k, v in data.items()}
                vsize = min(size, vcap)
            # the model thread's one wait on the device
            with TraceAnnotation("model.val_wait"):
                vloss = float(self._val_loss(self.params, vdata, vsize))
            self.stopper.update(vloss)
            self.epochs += 1
            self.model_server.push(self.params)         # Push
            return vloss


class PolicyImprovementWorker:
    """Algorithm 3. Pull φ -> ONE policy-improvement step (TRPO/PPO/MB-MPO
    on imagined rollouts) -> push θ.

    Keeps a device-resident model cache; an unchanged model version
    costs one lock + integer compare."""

    def __init__(self, algo, policy_server: ParameterServer,
                 model_server: ParameterServer, key, *, mesh=None,
                 batch_axis: Optional[str] = None, push_init: bool = True):
        """``push_init=False`` (procs-mode crash restart): suppress the
        initial random-policy push so a restarted worker can first load
        the latest snapshot and publish THAT instead — collectors never
        see a regression to fresh init params."""
        self.algo = algo
        self.policy_server = policy_server
        self.model_server = model_server
        self._key, k0 = jax.random.split(key)
        # role sub-mesh: imagination rollouts + TRPO batch statistics are
        # sharded over the policy sub-mesh; policy/model params replicated
        self._repl = None
        if mesh is not None:
            self._repl = ROLES.replicated(mesh)
            if hasattr(algo, "configure_mesh"):
                algo.configure_mesh(mesh, batch_axis)
        self.state = algo.init(k0)
        if self._repl is not None:
            self.state = jax.device_put(self.state, self._repl)
        if push_init:
            self.policy_server.push(self.state["policy"])
        self._model_cache = None
        self._model_ver = 0
        self.steps = 0

    def compile_count(self) -> int:
        """Compiled entries of the algo's one fused ``_improve`` jit
        (static shapes: steady state is exactly 1). -1 when the algo
        doesn't expose it — the chaos monitor then skips the check."""
        fn = getattr(self.algo, "_improve", None)
        return jit_cache_size(fn) if fn is not None else -1

    def step(self) -> bool:
        with TraceAnnotation("policy.step"):
            fresh, self._model_ver = self.model_server.pull_if_newer(
                self._model_ver, sharding=self._repl)   # Pull (gated)
            if fresh is not None:
                self._model_cache = _to_device(fresh)
            if self._model_cache is None:
                return False
            with TraceAnnotation("policy.improve"):
                self._key, k = jax.random.split(self._key)
                self.state, info = self.algo.improve(
                    self.state, self._model_cache, k)
            self.steps += 1
            self.policy_server.push(self.state["policy"])   # Push
            return True


# --------------------------------------------------------------- procs mode
#
# The paper's actual deployment shape: collector, model learner and
# policy improver as SEPARATE OS PROCESSES, so model/policy compute
# cannot steal cycles from the (real-time) collector even under the GIL.
# Everything below must stay picklable through the spawn context:
# plain-config dataclasses in, module-level entrypoint, IPC servers from
# servers.py. Heavy objects (env rollout jits, algos, ensembles) are
# REBUILT inside the child from ``(cfg, seed, role)``.

@dataclasses.dataclass
class ProcSpec:
    """Everything a spawned worker needs to rebuild its role locally:
    plain-dataclass configs + the shared seed. The child derives the
    same per-role keys as the in-process engines (split(key(seed), 4);
    fleet collectors additionally fold their id in — see
    ``collector_key``). Also the JOIN payload of the tcp transport:
    the parent publishes a pickled ProcSpec on its ControlPlane and a
    ``--connect`` joiner rebuilds a collector from it (net/join.py)."""
    env: Any                    # frozen env dataclass (picklable)
    ens_cfg: DYN.EnsembleConfig
    algo_cfg: Any               # mbrl.AlgoConfig
    pol_cfg: PI.PolicyConfig
    run_cfg: Any                # core.RunConfig
    seed: int
    exploration: Any = None     # ExplorationSchedule (or None: all 1.0)


@dataclasses.dataclass
class ProcChannels:
    """IPC endpoints shared by all three worker processes. The server
    handles are transport-blind (servers.ParameterTransport /
    DataTransport): shm/mp servers or tcp clients pickle through spawn
    identically, and the worker loops never know which they hold."""
    model_server: Any           # ParameterTransport (written by model)
    policy_server: Any          # ParameterTransport (written by policy)
    data: Any                   # DataTransport (collector -> model)
    trace_q: Any                # mp.Queue: eval-trace rows -> parent
    stop: Any                   # mp.Event: parent-ordered shutdown
    t0: float                   # parent's monotonic run start (shared
    #                             CLOCK_MONOTONIC: rows are run-relative)
    # liveness + invariant telemetry (chaos/soak, PR 7): a lock-free
    # mp.Array('d') of 2 doubles per heartbeat_slot — [last beat
    # monotonic, worker compile_count]. Single writer per slot (the
    # role's child); aligned 8-byte stores, so the parent's monitor
    # reads are never torn in practice. None = telemetry off (every
    # pre-chaos caller), all beats no-ops.
    heartbeat: Any = None

    def beat(self, slot: int, compiles: int = -1) -> None:
        """One worker-loop heartbeat: stamp the clock and publish the
        worker's current compile count. Cheap enough for every loop
        iteration (two array stores, no lock)."""
        hb = self.heartbeat
        if hb is None:
            return
        hb[2 * slot] = time.monotonic()
        hb[2 * slot + 1] = float(compiles)

    def read_heartbeat(self, slot: int):
        """(last_beat_monotonic, compile_count) for one slot — parent
        side. (0.0, 0.0) until the child's first beat."""
        hb = self.heartbeat
        if hb is None:
            return 0.0, 0.0
        return float(hb[2 * slot]), float(hb[2 * slot + 1])


def _load_snapshot(resume_dir, spec):
    """Latest COMPLETE parent snapshot as (tree, step) or (None, None).
    The template is rebuilt from configs via eval_shape — no device
    work. Corruption-tolerant: ``restore`` already skips truncated
    snapshots (newest-complete-first), and if NOTHING under the dir
    loads, a restarting worker starts fresh instead of crash-looping on
    a poisoned checkpoint (restart-under-fire, PR 7)."""
    import numpy as np

    from repro.checkpoint import io as ckpt_io
    if resume_dir is None or ckpt_io.latest_step(resume_dir) is None:
        return None, None
    template = {
        "model": jax.eval_shape(
            lambda: DYN.init_ensemble(spec.ens_cfg, jax.random.key(0))),
        "model_version": jax.ShapeDtypeStruct((), np.int64),
        "policy": jax.eval_shape(
            lambda: PI.init_policy(spec.pol_cfg, jax.random.key(0))),
        "policy_version": jax.ShapeDtypeStruct((), np.int64),
    }
    try:
        return ckpt_io.restore(resume_dir, template)
    except Exception:
        return None, None


def _proc_collector(spec, ch, key, collector_id: int = 0):
    rc = spec.run_cfg
    sched = spec.exploration or ExplorationSchedule()
    slot = heartbeat_slot(f"collector:{collector_id}", rc.n_collectors)
    w = DataCollectionWorker(spec.env, ch.policy_server, ch.data, None,
                             key, speed=rc.collect_speed,
                             collector_id=collector_id,
                             noise_scale=sched.scale_for(collector_id),
                             envs_per_step=rc.envs_per_collector)
    # warmup: don't claim a collection slot until a policy exists — a
    # claimed ticket must always be fulfilled by the very next step, or
    # the fleet's exact stopping criterion would stall on it
    while not ch.stop.is_set() and not w.poll_policy():
        ch.beat(slot, w.compile_count())
        time.sleep(0.005)
    # restart-safe stopping criterion: tickets live in the shared
    # ProcDataServer, so a restarted collector resumes the GLOBAL count
    # (the parent refunds the tickets of a crash-interrupted batch)
    while not ch.stop.is_set():
        ch.beat(slot, w.compile_count())
        g = ch.data.try_claim(collector_id, k=w.envs_per_step)
        if not g:
            break                   # global target fully claimed: done
        t_step = time.monotonic()
        try:
            dur = w.step(g)
        except Exception:
            if ch.stop.is_set():    # queue torn down mid-push: clean exit
                break
            raise
        if rc.pace_collection and dur is not None:
            # robot control frequency: one trajectory occupies `dur`
            # seconds of real time however fast the simulation computes
            time.sleep(max(dur - (time.monotonic() - t_step), 0.0))
    ch.beat(slot, w.compile_count())


def _proc_model(spec, ch, key, resume_dir):
    rc = spec.run_cfg
    w = ModelLearningWorker(spec.ens_cfg, ch.data, ch.model_server, key,
                            ema_weight=rc.ema_weight,
                            early_stop=rc.early_stop,
                            min_trajs=rc.min_warmup_trajs,
                            burst=default_burst(rc.n_collectors,
                                                rc.envs_per_collector))
    snap, _ = _load_snapshot(resume_dir, spec)
    if snap is not None:
        # crash restart: resume from the parent's latest checkpoint and
        # republish immediately — the policy worker sees a model version
        # NEWER than at crash time instead of waiting out a re-warmup.
        # (Optimizer state restarts fresh; the ring buffer refills from
        # the live trajectory queue.)
        w.params = _to_device(snap["model"])
        ch.model_server.push(w.params)
    slot = heartbeat_slot("model", rc.n_collectors)
    while not ch.stop.is_set():
        ch.beat(slot, w.compile_count())
        if w.step() is None:
            time.sleep(0.002)
    ch.beat(slot, w.compile_count())


def _proc_policy(spec, ch, key, keval, resume_dir):
    from repro.core.runtime import _Recorder
    from repro.mbrl.algos import make_algo
    rc = spec.run_cfg
    algo = make_algo(spec.algo_cfg, spec.pol_cfg,
                     jax.vmap(spec.env.reward), spec.env.reset_batch)
    # push_init=False: on a crash restart the snapshot policy must be
    # published FIRST — collectors never regress to fresh init params
    w = PolicyImprovementWorker(algo, ch.policy_server, ch.model_server,
                                key, push_init=False)
    snap, _ = _load_snapshot(resume_dir, spec)
    if snap is not None:
        w.state = {**w.state, "policy": _to_device(snap["policy"])}
    w.policy_server.push(w.state["policy"])
    rec = _Recorder(spec.env, rc.eval_rollouts)

    def record():
        nonlocal keval
        keval, k = jax.random.split(keval)
        rec.record(time.monotonic() - ch.t0, ch.data.total_pushed,
                   w.state["policy"], k)
        ch.trace_q.put(rec.trace[-1])

    slot = heartbeat_slot("policy", rc.n_collectors)
    n = 0
    while not ch.stop.is_set():
        ch.beat(slot, w.compile_count())
        if w.step():
            n += 1
            if n % rc.eval_every_policy_steps == 0:
                record()
        else:
            time.sleep(0.002)
    ch.beat(slot, w.compile_count())
    record()                        # final eval at shutdown


def proc_worker_main(role: str, spec: ProcSpec, ch: ProcChannels,
                     resume_dir: Optional[str] = None) -> None:
    """Picklable child entrypoint (spawn context). Each child initialises
    its OWN jax backend on import — nothing jax crosses the process
    boundary except host arrays through the IPC servers. Fleet
    collectors are addressed ``"collector:<id>"``; the id picks the
    collector's RNG stream and exploration rung."""
    enable_compile_cache()
    key = jax.random.key(spec.seed)
    _kc, _km, _kp, _keval = jax.random.split(key, 4)
    try:
        if role == "collector" or role.startswith("collector:"):
            cid = int(role.split(":", 1)[1]) if ":" in role else 0
            _proc_collector(spec, ch, _kc, cid)
        elif role == "model":
            _proc_model(spec, ch, _km, resume_dir)
        elif role == "policy":
            _proc_policy(spec, ch, _kp, _keval, resume_dir)
        else:
            raise ValueError(f"unknown role {role!r}")
    except KeyboardInterrupt:
        pass
    finally:
        # drop this child's shm mappings cleanly (non-owners never
        # unlink); otherwise cached np views make the interpreter-exit
        # __del__ spray BufferErrors
        for srv in (ch.model_server, ch.policy_server):
            try:
                srv.close()
            except Exception:
                pass
