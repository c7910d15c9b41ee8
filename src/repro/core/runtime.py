"""Training engines.

* ``AsyncTrainer`` — the paper's contribution (Fig. 1a). Three execution
  modes sharing the same worker objects, each able to run a FLEET of
  ``n_collectors`` data-collection workers (the paper's Fig. 4
  parallel-collection story; Gu et al.'s multi-robot fan-out) against
  the one global ``total_trajs`` criterion — ticket-claimed, so N
  racing collectors finish with exactly ``total_trajs`` trajectories:
    - ``mode="event"``: deterministic discrete-event simulation. Each
      worker has a virtual-time cursor; the engine always advances the
      worker with the SMALLEST cursor, so relative speeds (robot control
      frequency vs. compute) are reproduced exactly — this is how the
      paper's Figures 2/3/5 are regenerated on CPU CI.
    - ``mode="threads"``: real host threads + RealClock (shares one GIL
      and one jax runtime: model/policy compute still steals cycles
      from the collector).
    - ``mode="procs"``: separate OS processes (spawn context, one jax
      backend each) talking through shared-memory parameter stores and
      a trajectory queue (servers.ShmParameterServer/ProcDataServer) —
      the paper's actual claim, "run time ~= data collection time", on
      a real multicore host. The parent supervises: periodic
      params+version snapshots via checkpoint/io.py, dead children
      restarted from the latest snapshot (a crash degrades the run
      instead of hanging it). See ROADMAP.md "Process-isolation
      invariants (PR 4)".
* ``SequentialTrainer`` — the classic synchronous baseline (Fig. 1b).
* ``PartialAsyncModelPolicy`` — §5.2 ablation (interleave model/policy).
* ``PartialAsyncDataPolicy`` — §5.3 ablation (interleave data/policy).

All engines record an eval trace: list of dicts
(time, trajs, env_steps, eval_return) — one row per evaluation.
"""
from __future__ import annotations

import dataclasses
import gc
import queue as _queue
import tempfile
import threading
import time
from contextlib import ExitStack
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.roles import RoleSplit, split_roles
from repro.core.servers import (DataServer, ParameterServer, ProcDataServer,
                                ShmParameterServer)
from repro.core.workers import (DataCollectionWorker, ExplorationSchedule,
                                ModelLearningWorker,
                                PolicyImprovementWorker, ProcChannels,
                                ProcSpec, default_burst, heartbeat_slots,
                                proc_worker_main)
from repro.mbrl import dynamics as DYN
from repro.mbrl import policy as PI


@dataclasses.dataclass
class RunConfig:
    total_trajs: int = 40              # global stopping criterion (§4)
    eval_every_policy_steps: int = 5
    eval_rollouts: int = 4
    seed: int = 0
    # virtual durations for the event engine
    model_epoch_time: float = 1.0
    policy_step_time: float = 1.25   # ~GPU TRPO update on an imagined batch;
                                     # calibrated so async>=sync on all envs
                                     # (see benchmarks; Fig 5b still holds)
    collect_speed: float = 1.0         # Fig. 5b: 2.0 = twice as fast
    ema_weight: float = 0.9            # Fig. 5a
    early_stop: bool = True
    min_warmup_trajs: int = 4          # initial dataset before model pushes
    # collector fleet (ISSUE 5, the paper's Fig. 4 parallel-collection
    # story): N data-collection workers in every mode, sharing the ONE
    # global total_trajs criterion (ticket-claimed, so it lands exactly).
    # collect_noise optionally sets per-collector exploration noise
    # scales (cycled across the fleet); None = every collector at 1.0.
    n_collectors: int = 1
    collect_noise: Optional[tuple] = None
    # env farm (ISSUE 6): each collector simulates B envs per step via
    # one vmapped rollout (Env.rollout_batch) and pushes the whole batch
    # at once; tickets are claimed min(B, remaining) so the global
    # criterion still lands exactly. 1 = the pre-farm engine, bit for
    # bit (the single-rollout compiled program, one key split per step).
    envs_per_collector: int = 1
    # threads mode: sleep out each trajectory's robot time (horizon * dt /
    # collect_speed) so wall-clock reproduces the paper's real-robot rate
    # instead of racing simulated rollouts at compute speed
    pace_collection: bool = False
    # procs mode: how long a collector may block on a full trajectory
    # queue before ProcDataServer raises its descriptive
    # BackpressureError (servers.py)
    push_timeout_s: float = 30.0
    # procs mode: parent supervision — snapshot cadence for the
    # params+versions checkpoint (checkpoint/io.py), where to put it
    # (None -> fresh temp dir), and how many crash-restarts each worker
    # role gets before the run is declared failed
    snapshot_every_s: float = 2.0
    ckpt_dir: Optional[str] = None
    max_restarts: int = 3
    # threads/procs modes: after the collectors reach total_trajs, keep
    # the learners running until their servers reach these versions
    # (0 = stop immediately, the paper's pure criterion). A simulated
    # collector can outrun the learners' first XLA compile entirely; CI
    # and chip_smoke.py use this to assert the run actually trained.
    # The model worker only pushes after min_warmup_trajs, so never set
    # min_final_model_version > 0 with total_trajs < min_warmup_trajs.
    min_final_model_version: int = 0
    min_final_policy_version: int = 0
    # transport behind the servers (PR 9): "shm" keeps the in-process /
    # posix-shm fast path (zero-copy unchanged pulls, single host);
    # "tcp" routes every server through the socket control plane
    # (src/repro/net) — same version-gating and exact-criterion ticket
    # contracts across a machine boundary, and remote collectors may
    # join a live run via `--connect`. threads/procs modes only (the
    # event engine is a single-process simulation).
    transport: str = "shm"
    # tcp: "host:port" the control plane listens on. None = loopback
    # with an ephemeral port (tests, single-host runs); "0.0.0.0:5555"
    # publishes the plane for remote joiners.
    bind: Optional[str] = None


# One compiled eval program per (env, n_rollouts): every _Recorder used
# to build (and trace) its own jitted lambda, so each trainer instance
# paid a fresh compile for the same env — benchmarks build dozens.
# The cache is strongly keyed on the env VALUE (envs are small frozen
# dataclasses, so value-equal instances share one compiled program) but
# BOUNDED: LRU eviction caps it at _EVAL_CACHE_MAX entries and
# ``clear_eval_cache()`` empties it between benchmark sweep groups, so
# sweeping many env variants can no longer grow it without bound, and
# an evicted entry strands nothing (each _Recorder holds its own fn,
# which stays valid standalone). Weakref keying was tried and rejected:
# a weak key must compare like its referent to share across value-equal
# envs, but then ANY death order of sharers either evicts an entry a
# live trainer still needs or strands dead-keyed entries that can never
# be hit again.
_EVAL_CACHE: Dict[Any, Callable] = {}
_EVAL_CACHE_MAX = 64


def clear_eval_cache() -> None:
    """Drop every cached eval program (and the env values keying them).
    Benchmarks call this between sweep groups."""
    _EVAL_CACHE.clear()


def _eval_fn(env, eval_rollouts: int):
    cache_key = (env, eval_rollouts)
    fn = _EVAL_CACHE.pop(cache_key, None)   # pop + reinsert = LRU touch
    if fn is None:
        def eval_return(p, k):          # its executions: jit_eval_return
            return jnp.mean(jax.vmap(
                lambda kk: env.rollout(
                    kk, lambda pp, s, k2: PI.deterministic_action(pp, s),
                    p)["rew"].sum())(jax.random.split(k, eval_rollouts)))
        fn = jax.jit(eval_return)
    _EVAL_CACHE[cache_key] = fn
    while len(_EVAL_CACHE) > _EVAL_CACHE_MAX:   # dicts iterate insertion-
        del _EVAL_CACHE[next(iter(_EVAL_CACHE))]    # order: oldest first
    return fn


def _gc_span_hook():
    """A ``gc.callbacks`` hook that records each full (generation-2)
    collection as a span named ``gc``, from its start to its stop, on
    the thread that collects: a collection holds the interpreter lock,
    so it stops every other thread of the engine while it runs."""
    open_spans = []

    def hook(phase, info):
        if info.get("generation") != 2:
            return
        if phase == "start":
            span = TraceAnnotation("gc")
            span.__enter__()
            open_spans.append(span)
        elif open_spans:
            open_spans.pop().__exit__(None, None, None)
    return hook


class _Recorder:
    def __init__(self, env, eval_rollouts):
        self.env = env
        self.n = eval_rollouts
        self.trace: List[Dict[str, float]] = []
        self._eval = _eval_fn(env, eval_rollouts)

    def record(self, t, trajs, policy_params, key):
        ret = float(self._eval(policy_params, key))
        self.trace.append({"time": float(t), "trajs": int(trajs),
                           "env_steps": int(trajs * self.env.horizon),
                           "eval_return": ret})
        return ret


class Supervisor:
    """Hook seam into ``AsyncTrainer(mode="procs")`` supervision (PR 7).

    The parent's supervision loop calls these at well-defined points; the
    default implementation is a no-op, so plugging one in changes NOTHING
    about a healthy run. ``repro.chaos`` builds its fault-injection engine
    and always-on invariant monitor entirely on this seam — the trainer
    itself knows nothing about chaos.

    Lifecycle (all calls happen in the PARENT process):

    * ``attach(trainer)``      once, before any child is spawned.
    * ``on_spawn(role, proc, resume)``  after every child start
      (initial spawns and crash-restarts alike).
    * ``on_tick()``            every supervision-loop iteration (~50 Hz);
      the place to inject faults and check invariants DURING the run.
    * ``on_child_exit(role, exitcode, n_restarts)``  when the parent
      detects a dead child, BEFORE the budget check — fires even for the
      crash that exhausts the budget.
    * ``respawn_delay(role) -> float``  seconds to delay that role's
      crash-restart (0 = immediate, the pre-PR-7 behaviour). While
      delayed, the dead child stays visible in ``trainer._procs``.
    * ``on_snapshot(step)``    after every parent checkpoint attempt.
    * ``on_complete()``        when the stopping criterion is reached
      cleanly, before learner shutdown — last chance to un-stall
      children (SIGCONT) so the clean joins can proceed.
    * ``on_teardown(procs)``   FIRST thing in the teardown path, clean or
      not — must leave every child in a joinable state.
    """

    trainer: Any = None

    def attach(self, trainer) -> None:
        self.trainer = trainer

    def detach(self) -> None:
        """Drop the trainer reference. The trainer calls this LAST in
        its teardown: ``attach`` makes trainer<->supervisor a reference
        cycle, and breaking it lets refcounting free every mp primitive
        (locks, events, semaphore names in /dev/shm) the moment the
        caller releases the trainer — the ResourceAuditor's
        guaranteed-reclaim contract — instead of whenever the cycle
        collector next runs."""
        self.trainer = None

    def on_spawn(self, role: str, proc, resume: bool) -> None:
        pass

    def on_tick(self) -> None:
        pass

    def on_child_exit(self, role: str, exitcode: int,
                      n_restarts: int) -> None:
        pass

    def respawn_delay(self, role: str) -> float:
        return 0.0

    def on_snapshot(self, step: int) -> None:
        pass

    def on_complete(self) -> None:
        pass

    def on_teardown(self, procs: Dict[str, Any]) -> None:
        pass


class SupervisorChain(Supervisor):
    """Fan one supervision seam out to several supervisors (e.g. a chaos
    injector plus an invariant monitor). Hooks are called in order;
    ``respawn_delay`` is the MAX across members (the most patient member
    wins — a delayed respawn is the riskier schedule, which is what a
    chaos run wants to exercise)."""

    def __init__(self, *members: Supervisor):
        self.members = list(members)

    def attach(self, trainer) -> None:
        self.trainer = trainer
        for m in self.members:
            m.attach(trainer)

    def detach(self) -> None:
        self.trainer = None
        for m in self.members:
            m.detach()

    def on_spawn(self, role, proc, resume) -> None:
        for m in self.members:
            m.on_spawn(role, proc, resume)

    def on_tick(self) -> None:
        for m in self.members:
            m.on_tick()

    def on_child_exit(self, role, exitcode, n_restarts) -> None:
        for m in self.members:
            m.on_child_exit(role, exitcode, n_restarts)

    def respawn_delay(self, role) -> float:
        return max([m.respawn_delay(role) for m in self.members],
                   default=0.0)

    def on_snapshot(self, step) -> None:
        for m in self.members:
            m.on_snapshot(step)

    def on_complete(self) -> None:
        for m in self.members:
            m.on_complete()

    def on_teardown(self, procs) -> None:
        for m in self.members:
            m.on_teardown(procs)


class AsyncTrainer:
    def __init__(self, env, ens_cfg: DYN.EnsembleConfig, algo,
                 run_cfg: Optional[RunConfig] = None, *,
                 mode: str = "event", mesh=None,
                 roles: Optional[RoleSplit] = None,
                 role_ratios=(1, 2, 1), role_axis: Optional[str] = None,
                 algo_cfg=None, pol_cfg=None,
                 n_collectors: Optional[int] = None,
                 envs_per_collector: Optional[int] = None,
                 exploration: Optional[ExplorationSchedule] = None,
                 supervisor: Optional[Supervisor] = None):
        """``mesh``/``roles``: run each worker against its own role
        sub-mesh (core/roles.py). Pass a ``roles`` RoleSplit directly, or
        a ``mesh`` to split by ``role_ratios`` along ``role_axis``.
        Default (both None) is the single-device behaviour — all existing
        callers and the event engine are untouched.

        ``n_collectors``: size of the data-collection fleet (overrides
        ``run_cfg.n_collectors``). All three modes run N collectors
        against the one global ``total_trajs`` criterion; collector 0's
        RNG stream is identical to the lone collector's, so N=1 is
        bit-for-bit the pre-fleet engine. ``exploration`` plugs in a
        per-collector :class:`~repro.core.workers.ExplorationSchedule`
        (default: built from ``run_cfg.collect_noise``, or uniform 1.0).

        ``envs_per_collector``: the env farm (ISSUE 6) — each collector
        runs B simulated robots per step through one vmapped rollout
        (overrides ``run_cfg.envs_per_collector``; B=1 is the pre-farm
        engine bit for bit).

        ``mode="procs"`` additionally requires ``algo_cfg``/``pol_cfg``
        (plain-config AlgoConfig/PolicyConfig): spawned children cannot
        unpickle a built algo (it closes over jitted callables) — they
        rebuild it from configs. ``algo=None`` is then allowed and built
        here the same way (make_algo).

        ``supervisor``: a :class:`Supervisor` hooked into the procs-mode
        supervision loop (fault injection, invariant monitoring — see
        ``repro.chaos``). Procs-mode only."""
        if supervisor is not None and mode != "procs":
            raise ValueError(
                f'supervisor= hooks into the mode="procs" supervision '
                f"loop only (got mode={mode!r})")
        self.supervisor = supervisor
        if mode == "procs":
            if jax.default_backend() == "tpu":
                # the parent builds both learners here, so it holds the
                # chip before any child starts; a child then cannot get it
                raise ValueError(
                    'mode="procs" cannot run on TPU: the parent process '
                    "holds the chip and its spawned workers cannot reach "
                    'it. Use mode="threads" (one process, one chip).')
            if algo_cfg is None or pol_cfg is None:
                raise ValueError(
                    'mode="procs" needs algo_cfg= and pol_cfg= (children '
                    "rebuild the algorithm from plain configs)")
            if mesh is not None or roles is not None:
                raise ValueError(
                    'mode="procs" does not take a role mesh: each child '
                    "owns its whole local backend (per-process meshes "
                    "are future work, see ROADMAP.md)")
            if algo is None:
                from repro.mbrl.algos import make_algo
                algo = make_algo(algo_cfg, pol_cfg, jax.vmap(env.reward),
                                 env.reset_batch)
        self.algo_cfg = algo_cfg
        self.pol_cfg = pol_cfg
        self.ens_cfg = ens_cfg
        self.env = env
        # fresh per-instance config: a shared mutable default would leak
        # one caller's tweaks into every later trainer
        run_cfg = RunConfig() if run_cfg is None else run_cfg
        if n_collectors is not None:
            run_cfg = dataclasses.replace(run_cfg,
                                          n_collectors=int(n_collectors))
        if envs_per_collector is not None:
            run_cfg = dataclasses.replace(
                run_cfg, envs_per_collector=int(envs_per_collector))
        if run_cfg.n_collectors < 1:
            raise ValueError(f"n_collectors must be >= 1, got "
                             f"{run_cfg.n_collectors}")
        if run_cfg.envs_per_collector < 1:
            raise ValueError(f"envs_per_collector must be >= 1, got "
                             f"{run_cfg.envs_per_collector}")
        if run_cfg.transport not in ("shm", "tcp"):
            raise ValueError(f"transport must be 'shm' or 'tcp', got "
                             f"{run_cfg.transport!r}")
        if run_cfg.transport == "tcp" and mode == "event":
            raise ValueError(
                'transport="tcp" needs a real engine (mode="threads" or '
                '"procs"): the event engine is a single-process virtual-'
                "clock simulation with nothing to transport")
        self.run_cfg = run_cfg
        self.exploration = exploration if exploration is not None else (
            ExplorationSchedule(tuple(run_cfg.collect_noise))
            if run_cfg.collect_noise else ExplorationSchedule())
        self.mode = mode
        if roles is None and mesh is not None:
            roles = split_roles(mesh, ratios=tuple(role_ratios),
                                axis=role_axis)
        self.roles = roles
        key = jax.random.key(run_cfg.seed)
        kc, km, kp, self._keval = jax.random.split(key, 4)
        # transport seam (PR 9): threads + tcp runs every server through
        # ONE socket control plane — the workers are transport-blind
        # (identical method surface), only the handles change. Codecs
        # are fixed lazily from the first push (the workers that own the
        # templates are constructed just below). procs mode selects its
        # transport inside _run_procs; shm (default) is this block's
        # else-branch, bit for bit the previous engine.
        self._plane = None
        if run_cfg.transport == "tcp" and mode == "threads":
            from repro.net import ControlPlane
            self._plane = ControlPlane(run_cfg.bind or "127.0.0.1:0")
            self.model_server = self._plane.parameter_server("model")
            self.policy_server = self._plane.parameter_server("policy")
            self.data_server = self._plane.data_server(
                n_collectors=run_cfg.n_collectors,
                push_timeout=run_cfg.push_timeout_s)
        else:
            self.data_server = DataServer()
            self.model_server = ParameterServer()
            self.policy_server = ParameterServer()
        # workers shard batches along the axis the split was carved on
        # (NOT axis_names[0]: on a 2-pod mesh the split skips the 2-wide
        # 'pod' axis and carves 'data')
        self.policy_worker = PolicyImprovementWorker(
            algo, self.policy_server, self.model_server, kp,
            mesh=roles.policy if roles else None,
            batch_axis=roles.axis if roles else None)
        # the collector FLEET: every member shares the policy/data
        # servers but owns its RNG stream (collector 0 = the lone
        # collector's stream), its exploration rung, and — under a role
        # mesh — its own device of the collector sub-mesh (round-robin).
        # procs mode: the real fleet is rebuilt inside child processes
        # from ProcSpec, so the parent keeps ONE mirror collector (the
        # back-compat `collector` alias) instead of N idle jit wrappers.
        n_local = 1 if mode == "procs" else run_cfg.n_collectors
        self.collectors = [
            DataCollectionWorker(
                env, self.policy_server, self.data_server,
                self.policy_worker.state["policy"], kc,
                speed=run_cfg.collect_speed,
                mesh=roles.collector if roles else None,
                collector_id=i,
                noise_scale=self.exploration.scale_for(i),
                envs_per_step=run_cfg.envs_per_collector)
            for i in range(n_local)]
        self.collector = self.collectors[0]     # back-compat alias
        self.model_worker = ModelLearningWorker(
            ens_cfg, self.data_server, self.model_server, km,
            ema_weight=run_cfg.ema_weight, early_stop=run_cfg.early_stop,
            min_trajs=run_cfg.min_warmup_trajs,
            mesh=roles.model if roles else None,
            batch_axis=roles.axis if roles else None,
            burst=default_burst(run_cfg.n_collectors,
                                run_cfg.envs_per_collector))
        self.recorder = _Recorder(env, run_cfg.eval_rollouts)

    # ------------------------------------------------------------- event
    def run(self) -> List[Dict[str, float]]:
        try:
            if self.mode == "threads":
                return self._run_threads()
            if self.mode == "procs":
                return self._run_procs()
            return self._run_event()
        finally:
            # threads + tcp: the trainer owns the control plane for ONE
            # run. Snapshot the final versions/count (post-run asserts
            # read them), then shut the plane and its client handles —
            # this trainer is single-run, like every engine here.
            if self._plane is not None:
                try:
                    self.net_info = {
                        "model_version": int(self.model_server.version),
                        "policy_version": int(self.policy_server.version),
                        "trajs": int(self.data_server.total_pushed)}
                except Exception:
                    pass
                for srv in (self.model_server, self.policy_server,
                            self.data_server):
                    srv.close()
                self._plane.close()
                self._plane = None

    def _run_event(self):
        rc = self.run_cfg
        traj_t = (self.env.horizon * self.env.dt) / rc.collect_speed
        # cursors: virtual time at which each worker becomes free. The
        # FLEET gets one cursor per collector, so N collectors overlap
        # in virtual time exactly like N robots (Fig. 4) — and the
        # interleaving is deterministic per seed: ties resolve by dict
        # insertion order, every collector owns its RNG stream, so the
        # schedule (and the trace) is a pure function of the RunConfig.
        cur = {f"collect:{i}": 0.0 for i in range(len(self.collectors))}
        cur.update({"model": 0.0, "policy": 0.0})
        collect_t = (lambda: max(cur[f"collect:{i}"]
                                 for i in range(len(self.collectors))))
        ds = self.data_server
        since_eval = 0
        B = rc.envs_per_collector
        while ds.total_pushed < rc.total_trajs:
            w = min(cur, key=cur.get)
            t = cur[w]
            if w.startswith("collect:"):
                # env farm: B robots run in PARALLEL, so a batch step
                # still advances this collector's cursor by ONE
                # trajectory time. The single-threaded engine needs no
                # tickets — claim min(B, remaining) directly so the
                # criterion lands exactly when B doesn't divide it.
                g = min(B, rc.total_trajs - ds.total_pushed)
                self.collectors[int(w.split(":", 1)[1])].step(g)
                cur[w] = t + traj_t
            elif w == "model":
                out = self.model_worker.step()
                # idle model worker re-checks for data shortly
                cur[w] = t + (rc.model_epoch_time if out is not None
                              else min(traj_t, rc.model_epoch_time) * 0.5)
            else:
                did = self.policy_worker.step()
                cur[w] = t + (rc.policy_step_time if did
                              else min(traj_t, rc.policy_step_time) * 0.5)
                if did:
                    since_eval += 1
                    if since_eval >= rc.eval_every_policy_steps:
                        since_eval = 0
                        self._keval, k = jax.random.split(self._keval)
                        self.recorder.record(
                            collect_t(), ds.total_pushed,
                            self.policy_worker.state["policy"], k)
        # final eval at the end of collection
        self._keval, k = jax.random.split(self._keval)
        self.recorder.record(collect_t(), ds.total_pushed,
                             self.policy_worker.state["policy"], k)
        return self.recorder.trace

    # ----------------------------------------------------------- threads
    def _run_threads(self):
        rc = self.run_cfg
        stop = threading.Event()
        t0 = time.monotonic()   # all trace rows are relative to t0
        ds = self.data_server
        # fleet stopping criterion: each collector CLAIMS a slot before
        # collecting (one lock in the server), so the run finishes with
        # total_pushed EXACTLY total_trajs — N racing collectors can
        # never overshoot the paper's global criterion
        ds.set_target(rc.total_trajs)

        # a worker thread that raises would otherwise die with only a
        # stderr traceback: a dead collector strands its claimed tickets,
        # a dead learner leaves the run 'passing' at version 0. Record
        # (role, error), stop everyone, and re-raise the FIRST one from
        # the main thread after the joins.
        errors: List[tuple] = []

        def guarded(role, loop, *args):
            def run():
                try:
                    loop(*args)
                except Exception as e:
                    errors.append((role, e))
                    stop.set()
            return threading.Thread(target=run, daemon=True, name=role)

        def collect_loop(w):
            while not stop.is_set():
                # env farm: claim up to a whole batch of slots; the
                # server grants min(B, remaining), so the last batch
                # shrinks to land the criterion exactly
                g = ds.try_claim(w.collector_id, k=w.envs_per_step)
                if not g:
                    break
                t_step = time.monotonic()
                dur = w.step(g)
                if rc.pace_collection and dur is not None:
                    # emulate the robot's control frequency: a trajectory
                    # occupies `dur` seconds of real time regardless of
                    # how fast the simulated rollout computes
                    with TraceAnnotation("collector.pace"):
                        time.sleep(max(dur - (time.monotonic() - t_step),
                                       0.0))

        def model_loop():
            while not stop.is_set():
                if self.model_worker.step() is None:
                    with TraceAnnotation("model.idle"):
                        time.sleep(0.002)

        def policy_loop():
            n = 0
            while not stop.is_set():
                if self.policy_worker.step():
                    n += 1
                    if n % rc.eval_every_policy_steps == 0:
                        # the policy thread's one wait on the device
                        with TraceAnnotation("policy.eval"):
                            self._keval, k = jax.random.split(self._keval)
                            self.recorder.record(
                                time.monotonic() - t0, ds.total_pushed,
                                self.policy_worker.state["policy"], k)
                else:
                    with TraceAnnotation("policy.idle"):
                        time.sleep(0.002)

        collect_threads = [
            guarded(f"collector {w.collector_id}", collect_loop, w)
            for w in self.collectors]
        learner_threads = [guarded("model learner", model_loop),
                           guarded("policy learner", policy_loop)]
        gc_hook = _gc_span_hook()
        gc.callbacks.append(gc_hook)
        try:
            for th in collect_threads + learner_threads:
                th.start()
            for th in collect_threads:  # every claimed slot has been
                th.join()               # pushed once the whole fleet exits
            while not stop.is_set() and (
                    self.model_server.version < rc.min_final_model_version
                    or self.policy_server.version
                    < rc.min_final_policy_version):
                time.sleep(0.01)        # learners still owe their versions
            stop.set()
            for th in learner_threads:
                th.join(timeout=10)
        finally:
            gc.callbacks.remove(gc_hook)
        if errors:
            role, err = errors[0]
            raise RuntimeError(
                f"{role} failed mid-run; the fleet stopped at "
                f"{ds.total_pushed}/{rc.total_trajs} trajectories"
            ) from err
        self._keval, k = jax.random.split(self._keval)
        self.recorder.record(time.monotonic() - t0, ds.total_pushed,
                             self.policy_worker.state["policy"], k)
        return self.recorder.trace

    # ------------------------------------------------------------- procs
    def _drain_trace(self, trace_q) -> None:
        while True:
            try:
                self.recorder.trace.append(trace_q.get_nowait())
            except _queue.Empty:
                return

    def _snapshot(self, ckpt_dir, model_srv, policy_srv, step) -> int:
        """Checkpoint params+versions of both stores. Until a store's
        first push, its slot holds the (deterministic) init params at
        version 0 — restoring that is exactly 'restart from scratch'.

        A DEGRADED pull (None despite version > 0: the writer died
        mid-push, or pathological contention) must NOT be snapshotted —
        substituting init params there would ratchet the newest
        checkpoint back to scratch and a restarting worker would
        republish it over trained progress. Keep the previous snapshot
        instead and let the next cycle retry."""
        from repro.checkpoint import io as ckpt_io
        m, mv = model_srv.pull_host()
        p, pv = policy_srv.pull_host()
        if (m is None and model_srv.version > 0) or \
                (p is None and policy_srv.version > 0):
            return step
        if m is None:
            m, mv = jax.tree.map(np.asarray, self.model_worker.params), 0
        if p is None:
            p, pv = jax.tree.map(
                np.asarray, self.policy_worker.state["policy"]), 0
        tree = {"model": m, "model_version": np.int64(mv),
                "policy": p, "policy_version": np.int64(pv)}
        ckpt_io.save_pytree(ckpt_dir, tree, step=step, keep=3)
        return step + 1

    def _run_procs(self):
        import multiprocessing as mp
        rc = self.run_cfg
        sup = self.supervisor if self.supervisor is not None else Supervisor()
        ctx = mp.get_context("spawn")   # NEVER fork: the parent's jax
        #                                 runtime must not leak into
        #                                 children (fork corrupts XLA)
        ckpt_dir = Path(rc.ckpt_dir) if rc.ckpt_dir else \
            Path(tempfile.mkdtemp(prefix="repro_procs_ckpt_"))
        # every IPC resource is owned by this ExitStack: whatever path
        # leaves this method — clean completion, budget RuntimeError, a
        # KeyboardInterrupt mid-spawn — closes all three servers, so no
        # teardown relies on GC order (chaos invariant: the
        # ResourceAuditor sweeps /dev/shm + fds afterwards and must find
        # zero leaks even after a chaotic run)
        with ExitStack() as stack:
            # transport seam (PR 9): the supervision loop below is
            # TRANSPORT-BLIND — both families expose the same methods
            # (pull_host/version for snapshots and completion,
            # refund_inflight for crash refunds), so everything after
            # this block is identical for shm and tcp.
            plane = None
            if rc.transport == "tcp":
                from repro.net import ControlPlane
                plane = stack.enter_context(
                    ControlPlane(rc.bind or "127.0.0.1:0"))
                model_srv = stack.enter_context(
                    plane.parameter_server("model",
                                           self.model_worker.params))
                policy_srv = stack.enter_context(
                    plane.parameter_server(
                        "policy", self.policy_worker.state["policy"]))
                # same ticket arming as the mp queue below; counters
                # live on the plane, so remote joiners (--connect)
                # share the one exact criterion
                data_srv = stack.enter_context(plane.data_server(
                    n_collectors=rc.n_collectors, target=rc.total_trajs,
                    push_timeout=rc.push_timeout_s))
            else:
                model_srv = stack.enter_context(
                    ShmParameterServer(self.model_worker.params))
                policy_srv = stack.enter_context(
                    ShmParameterServer(self.policy_worker.state["policy"]))
                # ticket-armed: N collector processes claim collection
                # slots from the shared server, so the global criterion
                # lands exactly even across collector crashes (the
                # parent refunds in-flight tickets)
                data_srv = stack.enter_context(
                    ProcDataServer(ctx, n_collectors=rc.n_collectors,
                                   target=rc.total_trajs,
                                   push_timeout=rc.push_timeout_s))
            trace_q = ctx.Queue()
            # the trace queue's pipe fds are parent-held IPC too: close
            # them with the servers, not at GC time
            stack.callback(trace_q.close)
            stop = ctx.Event()
            # lock-free liveness/compile telemetry: one (beat_time,
            # compile_count) double pair per role slot, written by
            # children, read by the parent's invariant monitor
            hb = ctx.Array("d", 2 * heartbeat_slots(rc.n_collectors),
                           lock=False)
            ch = ProcChannels(model_srv, policy_srv, data_srv, trace_q,
                              stop, t0=time.monotonic(), heartbeat=hb)
            spec = ProcSpec(self.env, self.ens_cfg, self.algo_cfg,
                            self.pol_cfg, rc, rc.seed,
                            exploration=self.exploration)
            if plane is not None:
                # publish the spec for remote joiners (--connect): a
                # joining host rebuilds a collector from it and claims
                # from the same ticket counters as the local fleet
                import pickle as _pickle
                plane.set_join_spec(_pickle.dumps(spec))
            # exposed for tests/benchmarks/chaos: kill-and-restart pokes
            # _procs, the hotpath bench reads server versions while the
            # run is live, supervisors read channels + restart counters
            self._proc_servers = {"model": model_srv, "policy": policy_srv,
                                  "data": data_srv}
            self._proc_channels = ch
            # the fleet: one supervised child per collector, each with
            # its OWN restart budget ("collector:3" crashing repeatedly
            # must not eat the other collectors' allowance)
            collector_roles = [f"collector:{i}"
                               for i in range(rc.n_collectors)]
            restarts = {r: 0 for r in ["model", "policy"] + collector_roles}
            # restarts is shared LIVE (not copied) so a supervisor's
            # on_tick sees budget consumption as it happens
            self.proc_info: Dict[str, Any] = {
                "restarts": restarts, "ckpt_dir": str(ckpt_dir)}

            def spawn(role, resume=False):
                # children must re-import repro whatever launched the
                # parent (pytest, a notebook, an installed script)
                import os

                import repro

                # namespace package: __file__ is None, __path__ has dir
                pkg_dir = (repro.__file__ and Path(repro.__file__).parent) \
                    or Path(next(iter(repro.__path__)))
                src_root = str(Path(pkg_dir).resolve().parent)
                old_pp = os.environ.get("PYTHONPATH")
                if src_root not in (old_pp or "").split(os.pathsep):
                    os.environ["PYTHONPATH"] = \
                        src_root + (os.pathsep + old_pp if old_pp else "")
                try:
                    p = ctx.Process(
                        target=proc_worker_main, name=f"repro-{role}",
                        args=(role, spec, ch,
                              str(ckpt_dir) if resume else None),
                        daemon=True)
                    p.start()
                finally:
                    if old_pp is None:
                        os.environ.pop("PYTHONPATH", None)
                    else:
                        os.environ["PYTHONPATH"] = old_pp
                sup.on_spawn(role, p, resume)
                return p

            self._procs = {}
            # roles whose crash-restart a supervisor delayed: role ->
            # monotonic deadline. The dead child stays in _procs (its
            # nonzero exitcode keeps the completion check honest) until
            # the deadline passes and the respawn actually happens.
            pending_respawn: Dict[str, float] = {}
            last_snap = time.monotonic()
            snap_step = 0
            sup.attach(self)
            try:
                for r in ["policy", "model"] + collector_roles:
                    self._procs[r] = spawn(r)
                while True:
                    self._drain_trace(trace_q)
                    sup.on_tick()
                    if all(self._procs[r].exitcode == 0
                           for r in collector_roles) and \
                            model_srv.version >= \
                            rc.min_final_model_version and \
                            policy_srv.version >= \
                            rc.min_final_policy_version:
                        break       # stopping criterion reached cleanly
                    for role, p in list(self._procs.items()):
                        ec = p.exitcode
                        if ec is None or ec == 0:
                            continue
                        if role in pending_respawn:
                            # crash already accounted; respawn when due
                            if time.monotonic() < pending_respawn[role]:
                                continue
                            del pending_respawn[role]
                            self._procs[role] = spawn(role, resume=True)
                            continue
                        restarts[role] += 1
                        sup.on_child_exit(role, ec, restarts[role])
                        if restarts[role] > rc.max_restarts:
                            raise RuntimeError(
                                f"{role} worker crashed (exit {ec}) more "
                                f"than max_restarts={rc.max_restarts} "
                                "times")
                        p.join()
                        if role.startswith("collector:"):
                            # a crash between claim and push would strand
                            # a ticket and stall the criterion: refund it
                            data_srv.refund_inflight(
                                int(role.split(":", 1)[1]))
                        # restart from the LATEST snapshot: the child
                        # reloads params+versions via checkpoint/io.py —
                        # immediately, unless a supervisor asks for a
                        # delayed respawn (chaos: the run must survive a
                        # role being DOWN for a while, not just bouncing)
                        delay = float(sup.respawn_delay(role))
                        if delay > 0:
                            pending_respawn[role] = \
                                time.monotonic() + delay
                        else:
                            self._procs[role] = spawn(role, resume=True)
                    if time.monotonic() - last_snap >= rc.snapshot_every_s:
                        snap_step = self._snapshot(ckpt_dir, model_srv,
                                                   policy_srv, snap_step)
                        sup.on_snapshot(snap_step)
                        last_snap = time.monotonic()
                    time.sleep(0.02)
                sup.on_complete()   # un-stall anything before clean joins
                stop.set()
                for role in ("model", "policy"):
                    self._procs[role].join(timeout=120)
                # final eval row arrives AFTER the policy child saw stop
                try:
                    self.recorder.trace.append(trace_q.get(timeout=10))
                except _queue.Empty:
                    pass
                self._drain_trace(trace_q)
                # adopt the children's final published params so the
                # parent looks exactly like a threads-mode trainer after
                m_final, mv = model_srv.pull_host()
                p_final, pv = policy_srv.pull_host()
                if p_final is not None:
                    self.policy_worker.state = {
                        **self.policy_worker.state,
                        "policy": jax.tree.map(jnp.asarray, p_final)}
                    self.policy_server.push(
                        self.policy_worker.state["policy"])
                if m_final is not None:
                    self.model_worker.params = jax.tree.map(
                        jnp.asarray, m_final)
                    self.model_server.push(self.model_worker.params)
                self.collector.collected = data_srv.total_pushed
                snap_step = self._snapshot(ckpt_dir, model_srv, policy_srv,
                                           snap_step)
                self.proc_info.update({
                    "model_version": int(mv), "policy_version": int(pv),
                    "restarts": dict(restarts),
                    "trajs": data_srv.total_pushed,
                    "n_collectors": rc.n_collectors,
                    "noise_scales": [self.exploration.scale_for(i)
                                     for i in range(rc.n_collectors)]})
            finally:
                # FIRST: let the supervisor make children joinable again
                # (a chaos stall leaves a child SIGSTOPped — terminate()
                # sends SIGTERM, which a stopped process never handles)
                try:
                    sup.on_teardown(self._procs)
                except Exception:
                    pass
                stop.set()
                for p in self._procs.values():
                    if p.is_alive():
                        p.join(timeout=10)
                    if p.is_alive():
                        p.terminate()
                        p.join(timeout=5)
                    if p.is_alive():
                        p.kill()    # SIGKILL: even a wedged/stopped
                        p.join(timeout=5)   # child must not outlive us
                # break the trainer<->supervisor cycle so refcounting
                # frees every remaining mp primitive (heartbeat arena,
                # locks, semaphore names) as soon as the caller drops
                # the trainer — see Supervisor.detach
                sup.detach()
                # servers close via the ExitStack on every exit path
        return self.recorder.trace


class SequentialTrainer:
    """Classic synchronous MBRL (Fig. 1b): collect N -> fit model to
    convergence (early stop / max epochs) -> G policy steps -> repeat."""

    def __init__(self, env, ens_cfg, algo,
                 run_cfg: Optional[RunConfig] = None,
                 *, n_rollouts: int = 5, max_model_epochs: int = 50,
                 policy_steps: int = 20):
        self.env = env
        run_cfg = RunConfig() if run_cfg is None else run_cfg
        self.run_cfg = run_cfg
        self.n_rollouts = n_rollouts
        self.max_model_epochs = max_model_epochs
        self.policy_steps = policy_steps
        key = jax.random.key(run_cfg.seed)
        kc, km, kp, self._keval = jax.random.split(key, 4)
        self.data_server = DataServer()
        self.model_server = ParameterServer()
        self.policy_server = ParameterServer()
        self.policy_worker = PolicyImprovementWorker(
            algo, self.policy_server, self.model_server, kp)
        self.collector = DataCollectionWorker(
            env, self.policy_server, self.data_server,
            self.policy_worker.state["policy"], kc)
        self.model_worker = ModelLearningWorker(
            ens_cfg, self.data_server, self.model_server, km,
            ema_weight=run_cfg.ema_weight, early_stop=run_cfg.early_stop,
            min_trajs=run_cfg.min_warmup_trajs)
        self.recorder = _Recorder(env, run_cfg.eval_rollouts)

    def run(self):
        rc = self.run_cfg
        t = 0.0
        traj_t = self.env.horizon * self.env.dt
        while self.collector.collected < rc.total_trajs:
            for _ in range(self.n_rollouts):
                self.collector.step()
                t += traj_t
            self.model_worker.stopper.reset()
            for _ in range(self.max_model_epochs):
                out = self.model_worker.step()
                if out is None:
                    break
                t += rc.model_epoch_time
            for i in range(self.policy_steps):
                if self.policy_worker.step():
                    t += rc.policy_step_time
            self._keval, k = jax.random.split(self._keval)
            self.recorder.record(t, self.collector.collected,
                                 self.policy_worker.state["policy"], k)
        return self.recorder.trace


class PartialAsyncModelPolicy(SequentialTrainer):
    """§5.2: collect N rollouts, then ALTERNATE (1 model epoch, G' policy
    steps) — policy sees models before they converge."""

    def run(self):
        rc = self.run_cfg
        t = 0.0
        traj_t = self.env.horizon * self.env.dt
        g_alt = max(self.policy_steps // self.max_model_epochs, 1)
        while self.collector.collected < rc.total_trajs:
            for _ in range(self.n_rollouts):
                self.collector.step()
                t += traj_t
            self.model_worker.stopper.reset()
            for e in range(self.max_model_epochs):
                out = self.model_worker.step()
                if out is not None:
                    t += rc.model_epoch_time
                for _ in range(g_alt):
                    if self.policy_worker.step():
                        t += rc.policy_step_time
                if out is None:
                    break
            self._keval, k = jax.random.split(self._keval)
            self.recorder.record(t, self.collector.collected,
                                 self.policy_worker.state["policy"], k)
        return self.recorder.trace


class PartialAsyncDataPolicy(SequentialTrainer):
    """§5.3: fit the model, then ALTERNATE (G policy steps, collect one
    rollout) N times — collection uses fresh mid-training policies."""

    def run(self):
        rc = self.run_cfg
        t = 0.0
        traj_t = self.env.horizon * self.env.dt
        g_alt = max(self.policy_steps // max(self.n_rollouts, 1), 1)
        # initial data
        for _ in range(self.n_rollouts):
            self.collector.step()
            t += traj_t
        while self.collector.collected < rc.total_trajs:
            self.model_worker.stopper.reset()
            for _ in range(self.max_model_epochs):
                out = self.model_worker.step()
                if out is None:
                    break
                t += rc.model_epoch_time
            for _ in range(self.n_rollouts):
                for _ in range(g_alt):
                    if self.policy_worker.step():
                        t += rc.policy_step_time
                self.collector.step()
                t += traj_t
            self._keval, k = jax.random.split(self._keval)
            self.recorder.record(t, self.collector.collected,
                                 self.policy_worker.state["policy"], k)
        return self.recorder.trace
