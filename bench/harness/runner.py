"""Drive the program's own engine: build, set up to a fixed point, run
one measured window.

The window is ``AsyncTrainer(mode="threads").run()`` itself — collector
farm, data server, model learner, policy learner and the parameter
servers — in this process, which holds the chip. The benchmark only
wraps each worker's ``step`` (and the servers' ``push`` and ``drain``)
on the instance, to time it and to name it in the profiler's trace; no
wrapper waits on the device.

Set-up brings the run to a point fixed by counts, whatever it costs in
time: ``n_fill`` rounds of (one farm step of every collector, one model
step), which fills the ring's train and validation parts, then three
policy steps. These are the first steps of the very objects the window
drives, through the same calls; the correctness check compares them
with the reference (``reference.py``). ``total_trajs`` is out of reach,
and the window ends through the engine's own criterion: the data
server's target is set to what has landed, the collectors stop claiming,
and the engine joins its threads.
"""
from __future__ import annotations

import collections
import dataclasses
import math
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

OUT_OF_REACH = 2 ** 31 - 1      # total_trajs: the window ends it instead
CHECKED_STEPS = 3               # learner steps the reference follows


def host(tree):
    return jax.tree.map(lambda x: np.array(x), tree)


def fill_rounds(config: dict, robots: int) -> int:
    """Rounds of farm steps that fill the ring's train and validation
    parts, as the program splits them (every round(1/holdout)-th
    trajectory is held out; validation holds a quarter of the rows)."""
    every = max(int(round(1 / config["holdout_frac"])), 2)
    need_train = config["ring_trajs"]
    need_val = max(config["ring_trajs"] // 4, 1)
    t = 0
    while t - t // every < need_train or t // every < need_val:
        t += robots
    return max(t // robots, CHECKED_STEPS)


def algo_config(config: dict):
    """The program's ``AlgoConfig`` from every field of it that the
    configuration names (``algo`` is required; the rest keep the
    program's defaults)."""
    from repro.mbrl import AlgoConfig
    names = {f.name for f in dataclasses.fields(AlgoConfig)}
    named = {k: v for k, v in config.items() if k in names}
    return AlgoConfig(**{**named, "algo": config["algo"]})


def build(config: dict, traffic: dict, seed: int):
    """The trainer ``launch/train.py --task mbrl`` builds, at the
    configuration's sizes."""
    from repro.core import AsyncTrainer, RunConfig
    from repro.envs import make_env
    from repro.mbrl import EnsembleConfig, PolicyConfig, make_algo
    env = make_env(config["env"])
    dims = (env.obs_dim, env.act_dim, env.horizon, round(1 / env.dt))
    want = (config["obs_dim"], config["act_dim"], config["horizon"],
            config["control_hz"])
    if dims != want:
        raise ValueError(f"env {config['env']}: (obs, act, horizon, Hz) "
                         f"{dims} != configuration {want}")
    ens = EnsembleConfig(env.obs_dim, env.act_dim,
                         hidden=config["model_hidden"],
                         depth=config["model_depth"],
                         n_models=config["n_models"], lr=config["model_lr"],
                         train_batch=config["model_train_batch"],
                         holdout_frac=config["holdout_frac"])
    pol = PolicyConfig(env.obs_dim, env.act_dim,
                       hidden=config["policy_hidden"],
                       depth=config["policy_depth"],
                       init_log_std=config["policy_init_log_std"])
    acfg = algo_config(config)
    algo = make_algo(acfg, pol, jax.vmap(env.reward), env.reset_batch)
    rc = RunConfig(total_trajs=OUT_OF_REACH, seed=seed,
                   n_collectors=traffic["collectors"],
                   envs_per_collector=traffic["robots_per_collector"],
                   ema_weight=config["ema_weight"], early_stop=True,
                   eval_every_policy_steps=config["eval_every_policy_steps"],
                   eval_rollouts=config["eval_rollouts"],
                   pace_collection=False)
    mesh = None
    if "mesh" in traffic:       # role-sharded: one mesh split by roles
        from repro.launch.mesh import make_mesh
        mesh = make_mesh(tuple(traffic["mesh"]), ("data",))
    tr = AsyncTrainer(env, ens, algo, rc, mode="threads", algo_cfg=acfg,
                      pol_cfg=pol, mesh=mesh,
                      role_ratios=tuple(traffic.get("role_ratios",
                                                    (1, 2, 1))))
    # the ring is built lazily from this on the first drain
    tr.model_worker.max_trajs = config["ring_trajs"]
    return tr


def ring_cover(config: dict) -> int:
    """The fewest consecutive trajectories that rewrite both rings
    whole, wherever the holdout split stands when they start."""
    every = max(int(round(1 / config["holdout_frac"])), 2)
    need_val = max(config["ring_trajs"] // 4, 1)
    t = 0
    while (t // every < need_val
           or t - math.ceil(t / every) < config["ring_trajs"]):
        t += 1
    return t


# ----------------------------------------------------------------- set-up
def setup(tr, config: dict, traffic: dict) -> dict:
    """Drive the trainer from its seed to the window's starting point.

    Returns the program's outputs of those steps, on the host: the farm's
    trajectories, the ring, the model learner's validation losses, Adam
    state after its first step and parameters before and after three
    steps, and the policy learner's states and imagined returns."""
    robots = traffic["robots_per_collector"]
    rounds = fill_rounds(config, robots * traffic["collectors"])
    mw, pw, ds = tr.model_worker, tr.policy_worker, tr.data_server
    out = {"rounds": rounds, "trajs": [], "val_loss": [],
           "imagined_return": []}

    landed = []
    push_batch = ds.push_batch

    def recording_push(batch, n, **kw):
        landed.append(host(batch))
        return push_batch(batch, n, **kw)

    ds.push_batch = recording_push
    out["model0"] = host(mw.params)
    for r in range(rounds):
        for c in tr.collectors:
            c.step()
        v = mw.step()
        if v is None:
            raise RuntimeError(f"model learner idled on set-up round {r}")
        out["val_loss"].append(float(v))
        if r == 0:
            out["model_opt1"] = host(mw.opt_state.mu)
        if r == CHECKED_STEPS - 1:
            out["model3"] = host(mw.params)
    del ds.push_batch
    out["trajs"] = {k: np.concatenate([b[k] for b in landed])
                    for k in landed[0]}
    data, size = mw.buffer.train_view()
    vdata, vsize = mw.buffer.val_view()
    out["ring"] = host(data)
    out["ring_val"] = host(vdata)
    out["ring_size"], out["ring_val_size"] = int(size), int(vsize)
    out["ring_full"] = (size == mw.buffer.capacity
                        and vsize == mw.buffer.val_capacity)

    algo = pw.algo
    improve = algo.improve

    def recording_improve(state, model, key):
        new, info = improve(state, model, key)
        out["imagined_return"].append(float(info["imagined_return"]))
        return new, info

    algo.improve = recording_improve
    out["policy0"] = host(pw.state)
    for s in range(CHECKED_STEPS):
        if not pw.step():
            raise RuntimeError("policy learner found no model in set-up")
        if s == 0:
            out["policy1"] = host(pw.state)
        if s == CHECKED_STEPS - 1:
            out["policy3"] = host(pw.state)
    del algo.improve
    out["model_version_seen"] = int(pw._model_ver)

    # programs the window calls that set-up has not: the eval rollout
    # the policy loop runs every few steps, and its key split (on a key
    # of the benchmark's, so the engine's own stream is untouched)
    _, k = jax.random.split(jax.random.key(0))
    jax.block_until_ready(tr.recorder._eval(pw.state["policy"], k))
    return out


def warm_ingest(tr, traffic: dict, drains=(1, 2, 3, 4)) -> None:
    """Compile the ring ingest for drains of 1..4 farm batches on a
    scratch ring, so an unpaced window whose model learner drains
    several landings at once compiles nothing. The ring of the run is
    untouched."""
    from repro.core.servers import ReplayBuffer
    mw = tr.model_worker
    data, _ = mw.buffer.train_view()
    lane = {k: jnp.zeros((tr.env.horizon,) + v.shape[1:], v.dtype)
            for k, v in data.items()}
    b = traffic["robots_per_collector"] * traffic["collectors"]
    for n in drains:
        for start in range(max(mw.buffer._every, 1)):
            rb = ReplayBuffer(mw.buffer.capacity,
                              val_capacity=mw.buffer.val_capacity,
                              holdout_frac=mw.buffer.holdout_frac,
                              sharding=mw._batch_shard,
                              burst_capacity=mw.burst)
            rb._trajs = start           # every phase of the holdout split
            rb.extend([lane] * (n * b))
            jax.block_until_ready(rb.train_view()[0])


# ----------------------------------------------------------------- window
class Events:
    """What the window's wrappers saw, on the host clock
    (``time.perf_counter``)."""

    def __init__(self):
        self.farm = []          # (start, end) of each farm step
        self.landed = []        # (time, trajectories) of each landing
        self.pulls = []         # (time, policy version) at each farm pull
        self.model = []         # (start, end, trained?) of model steps
        self.ingest = []        # (start, end) of each drain into the ring
        self.policy = []        # (start, end, stepped?) of policy steps
        self.policy_push = {}   # policy version -> push time
        self.compiles = []      # (time, name) of every executable built
        self.drained = collections.deque()  # last trajectories drained
        self.drains = []        # trajectories moved by each drain


def _span(name, fn, log):
    def wrapped(*a, **kw):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            r = fn(*a, **kw)
        log(t0, time.perf_counter(), r)
        return r
    return wrapped


def instrument(tr, ev: Events, config: dict) -> None:
    """Wrap the workers' steps, the servers' pushes and the model
    learner's drain on the instances.

    No wrapper waits on the device: a step ends when the program's step
    returns, after its push, as the program sees it. The program waits
    only at its evaluations (every few policy steps) and the model
    learner's validation loss, so its host runs at most that far ahead
    of the device. The drain keeps references to the last trajectories
    it moved into the ring (``ring_cover``), without a copy."""
    for c in tr.collectors:
        c.step = _span("farm_step", c.step,
                       lambda a, b, r: ev.farm.append((a, b)))
        poll = c.poll_policy

        def timed_poll(c=c, poll=poll):
            ok = poll()
            ev.pulls.append((time.perf_counter(), int(c._policy_ver)))
            return ok
        c.poll_policy = timed_poll
    ds = tr.data_server
    push_batch = ds.push_batch

    def landing(batch, n, **kw):
        r = push_batch(batch, n, **kw)
        ev.landed.append((time.perf_counter(), int(n)))
        return r
    ds.push_batch = landing
    drain = ds.drain
    ev.drained = collections.deque(maxlen=ring_cover(config))

    def recording_drain():          # the model learner's thread alone
        items = drain()
        if items:
            ev.drained.extend(items)
            ev.drains.append(len(items))
        return items
    ds.drain = recording_drain
    mw = tr.model_worker
    mw._refresh_data = _span("ring_ingest", mw._refresh_data,
                             lambda a, b, r: r and ev.ingest.append((a, b)))
    mw.step = _span("model_step", mw.step,
                    lambda a, b, r: ev.model.append((a, b, r is not None)))
    pw = tr.policy_worker
    pw.step = _span("policy_step", pw.step,
                    lambda a, b, r: ev.policy.append((a, b, bool(r))))
    ps = tr.policy_server
    ppush = ps.push

    def timed_push(value):
        v = ppush(value)
        ev.policy_push[v] = time.perf_counter()
        return v
    ps.push = timed_push


_compile_log = None


def _on_duration(event, duration, **kw):
    if _compile_log is not None and event.endswith("backend_compile_duration"):
        _compile_log.append((time.perf_counter(), kw.get("fun_name", "?")))


def watch_compiles(ev: Events) -> None:
    """Log every executable built or loaded from now on."""
    global _compile_log
    if _compile_log is None:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
    _compile_log = ev.compiles


def run_window(tr, traffic: dict, seconds: float, ev: Events,
               trace_dir=None, trace_seconds: float = 4.0,
               join_s: float = 120.0) -> dict:
    """Open the window, let the engine run ``seconds``, close it through
    the data server's target, and join the engine. With ``trace_dir``
    the profiler records the window's last ``trace_seconds`` (a span
    named ``traced``): a whole window's trace is too large to read back
    within a run."""
    tr.run_cfg.pace_collection = bool(traffic["paced"])
    result = {}

    def engine():
        try:
            tr.run()
        except BaseException as e:         # re-raised in the caller
            result["error"] = e

    th = threading.Thread(target=engine, name="engine", daemon=True)
    t_open = time.perf_counter()
    th.start()
    if trace_dir is not None:
        lead = max(seconds - trace_seconds, 0.0)
        time.sleep(max(t_open + lead - time.perf_counter(), 0.0))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # spans and device events only
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        with jax.profiler.TraceAnnotation("traced"):
            time.sleep(max(t_open + seconds - time.perf_counter(), 0.0))
    time.sleep(max(t_open + seconds - time.perf_counter(), 0.0))
    t_close = time.perf_counter()
    ds = tr.data_server
    ds.set_target(ds.total_pushed)        # the engine's own criterion
    if trace_dir is not None:
        jax.profiler.stop_trace()
    th.join(join_s)
    if th.is_alive():
        raise RuntimeError(f"the engine did not stop within {join_s} s "
                           f"of the window's close")
    if "error" in result:
        raise result["error"]
    return {"open": t_open, "close": t_close}


def window_ring(tr, ev: Events, prog: dict) -> dict:
    """The ring as the window left it, with the trajectories that went
    into it last: set-up's, then those the window's drains moved, of
    every drain size the window ran. ``start`` counts the trajectories
    ingested before the first of them. Read once the engine has
    joined."""
    mw = tr.model_worker
    win = jax.device_get(list(ev.drained))
    seq = {k: np.concatenate([prog["trajs"][k]]
                             + ([np.stack([t[k] for t in win])]
                                if win else []))
           for k in prog["trajs"]}
    keep = ev.drained.maxlen
    seq = {k: v[-keep:] for k, v in seq.items()}
    total = len(prog["trajs"]["obs"]) + sum(ev.drains)
    data, size = mw.buffer.train_view()
    vdata, vsize = mw.buffer.val_view()
    return {"trajs": seq, "start": total - len(seq["obs"]),
            "seen": mw.buffer.total_seen, "ingested": total,
            "ring": host(data), "ring_val": host(vdata),
            "full": (size == mw.buffer.capacity
                     and vsize == mw.buffer.val_capacity)}


# ------------------------------------------------------------ end to end
def _in(t, w):
    return w["open"] <= t <= w["close"]


def policy_steps_per_s(ev: Events, w: dict):
    """Whole steps between the first and the last completion inside the
    window, over the time between those two completions. A step completes
    when it has pushed its policy; the device may still run its last few
    steps (up to the next evaluation's wait)."""
    done = [b for a, b, ok in ev.policy if ok and _in(b, w)]
    if len(done) < 2:
        return None
    return (len(done) - 1) / (done[-1] - done[0])


def trajs_per_s(ev: Events, w: dict):
    """Trajectories landed after the first landing inside the window, over
    the time from the first to the last landing."""
    landed = [(t, k) for t, k in ev.landed if _in(t, w)]
    if len(landed) < 2:
        return None
    return sum(k for t, k in landed[1:]) / (landed[-1][0] - landed[0][0])


def policy_ages(ev: Events, w: dict) -> list:
    return [t - ev.policy_push[v] for t, v in ev.pulls
            if _in(t, w) and v in ev.policy_push]


def counts(ev: Events, w: dict) -> dict:
    return {
        "policy_steps": sum(1 for a, b, ok in ev.policy if ok and _in(b, w)),
        "model_epochs": sum(1 for a, b, ok in ev.model if ok and _in(b, w)),
        "model_idle_polls": sum(1 for a, b, ok in ev.model
                                if not ok and _in(b, w)),
        "farm_steps": sum(1 for a, b in ev.farm if _in(b, w)),
        "landings": sum(1 for t, k in ev.landed if _in(t, w)),
        "trajs_landed": sum(k for t, k in ev.landed if _in(t, w)),
        "ingests": sum(1 for a, b in ev.ingest if _in(b, w)),
        "drain_sizes": dict(sorted(collections.Counter(ev.drains).items())),
        "compiles_in_window": sum(1 for t, n in ev.compiles if _in(t, w)),
        "compiled_in_window": sorted({n for t, n in ev.compiles
                                      if _in(t, w)}),
        "policy_ages": len(policy_ages(ev, w)),
        "policy_age_s": {f"p{q}": float(np.quantile(policy_ages(ev, w),
                                                     q / 100))
                         for q in (50, 80, 90, 95)
                         if policy_ages(ev, w)},
    }


def device_info(chips: int) -> dict:
    devs = jax.devices()
    d = devs[0]
    peak = max((x.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for x in devs[:chips])
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def finite(tree) -> bool:
    return all(bool(np.isfinite(np.asarray(x)).all())
               for x in jax.tree.leaves(tree))

