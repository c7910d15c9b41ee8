"""Unified launcher.

Two entry modes:

* ``--task mbrl`` (the paper): asynchronous model-based RL on a pure-JAX
  env with ME-TRPO / ME-PPO / MB-MPO, async or sequential engines.

      python -m repro.launch.train --task mbrl --env pendulum \
          --algo me-trpo --engine async --trajs 60

* ``--task lm``: world-model / LM pre-training step loop for any assigned
  architecture (reduced configs run on CPU; full configs expect a pod).

      python -m repro.launch.train --task lm --arch glm4-9b --reduced \
          --steps 20 --seq 128 --batch 8
"""
from __future__ import annotations

import argparse
import json
import time
from contextlib import nullcontext

import jax
import jax.numpy as jnp


def build_mesh(spec: str):
    """``--mesh`` -> Mesh: "none" (single-device), "auto" (all local
    devices on one ("data",) axis), or an explicit device count "8"
    (errors if unavailable — combine with
    XLA_FLAGS=--xla_force_host_platform_device_count=N on CPU)."""
    if spec == "none":
        return None
    from repro.launch.mesh import make_mesh
    n = jax.device_count() if spec == "auto" else int(spec)
    return make_mesh((n,), ("data",))


def run_mbrl(args):
    from repro.core import (AsyncTrainer, PartialAsyncDataPolicy,
                            PartialAsyncModelPolicy, RunConfig,
                            SequentialTrainer)
    from repro.envs import make_env
    from repro.mbrl import (AlgoConfig, EnsembleConfig, PolicyConfig,
                            make_algo)

    mesh = build_mesh(args.mesh)
    role_ratios = tuple(int(x) for x in args.role_ratios.split(","))
    if mesh is not None and args.engine != "async":
        raise SystemExit("--mesh is only supported by --engine async "
                         "(role meshes belong to the async engine)")
    env = make_env(args.env)
    ens = EnsembleConfig(env.obs_dim, env.act_dim, hidden=args.model_hidden,
                         n_models=args.n_models)
    pol = PolicyConfig(env.obs_dim, env.act_dim, hidden=args.policy_hidden)
    acfg = AlgoConfig(algo=args.algo, imagine_batch=args.imagine_batch,
                      imagine_horizon=args.imagine_horizon,
                      n_models=args.n_models)
    algo = make_algo(acfg, pol, jax.vmap(env.reward), env.reset_batch)
    collect_noise = (tuple(float(x) for x in args.collect_noise.split(","))
                     if args.collect_noise else None)
    rc = RunConfig(total_trajs=args.trajs, seed=args.seed,
                   collect_speed=args.collect_speed,
                   ema_weight=args.ema_weight,
                   early_stop=not args.no_early_stop,
                   ckpt_dir=args.ckpt_dir,
                   n_collectors=args.n_collectors,
                   collect_noise=collect_noise,
                   envs_per_collector=args.envs_per_collector,
                   transport=args.transport, bind=args.bind)
    if args.transport == "tcp" and args.engine != "async":
        raise SystemExit("--transport tcp needs --engine async "
                         "(the control plane serves the async servers)")
    if args.n_collectors > 1 and args.engine != "async":
        raise SystemExit("--n-collectors > 1 needs --engine async "
                         "(collector fleets belong to the async engine)")
    if args.envs_per_collector > 1 and args.engine != "async":
        raise SystemExit("--envs-per-collector > 1 needs --engine async "
                         "(env farms belong to the async engine)")
    if args.mode == "procs" and args.engine != "async":
        raise SystemExit("--mode procs is only meaningful with "
                         "--engine async")
    engines = {
        # procs children rebuild the algo from plain configs, so the
        # async engine gets them alongside the built algo object
        "async": lambda: AsyncTrainer(env, ens, algo, rc, mode=args.mode,
                                      mesh=mesh, role_ratios=role_ratios,
                                      algo_cfg=acfg, pol_cfg=pol),
        "sequential": lambda: SequentialTrainer(env, ens, algo, rc),
        "partial-model": lambda: PartialAsyncModelPolicy(env, ens, algo, rc),
        "partial-data": lambda: PartialAsyncDataPolicy(env, ens, algo, rc),
    }
    tr = engines[args.engine]()
    t0 = time.perf_counter()  # monotonic: an NTP step must not skew this
    profile = nullcontext()
    if args.profile_dir:
        # the program's spans and the device's executions, on one clock
        # (README, "Tracing a run")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # spans and device events only
        profile = jax.profiler.trace(args.profile_dir,
                                     profiler_options=opts)
    with profile:
        trace = tr.run()
    out = {"engine": args.engine, "algo": args.algo, "env": args.env,
           "real_seconds": round(time.perf_counter() - t0, 1),
           "trace": trace}
    if getattr(tr, "roles", None) is not None:
        out["roles"] = tr.roles.describe()
    if getattr(tr, "collectors", None) is not None:
        # fleet report: each member's exploration rung and — for the
        # in-process engines — its share of the global criterion (the
        # procs fleet lives in child processes; its counts are global
        # only, reported in the "procs" block below)
        n = tr.run_cfg.n_collectors
        out["fleet"] = {
            "n_collectors": n,
            "envs_per_collector": tr.run_cfg.envs_per_collector,
            "sim_robots": n * tr.run_cfg.envs_per_collector,
            "noise_scales": [tr.exploration.scale_for(i)
                             for i in range(n)],
        }
        if args.mode != "procs":
            out["fleet"]["trajs_per_collector"] = \
                [c.collected for c in tr.collectors]
    if getattr(tr, "proc_info", None):
        out["procs"] = tr.proc_info
    print(json.dumps(out["trace"][-1], indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
        print("wrote", args.out)
    return trace


def run_join(args):
    """``--connect host:port``: no training here — this process donates
    ``--n-collectors`` remote collectors to a live run's control plane
    and exits when the run's global criterion is fully claimed."""
    from repro.net import join_as_collectors
    t0 = time.perf_counter()
    n = join_as_collectors(args.connect, n_collectors=args.n_collectors)
    print(json.dumps({"connect": args.connect,
                      "n_collectors": args.n_collectors,
                      "trajs_contributed": n,
                      "real_seconds": round(time.perf_counter() - t0, 1)},
                     indent=1))
    return n


def run_lm(args):
    from repro.configs import get_config, registry
    from repro.launch.mesh import make_smoke_mesh
    from repro.models import api
    from repro.models.config import InputShape
    from repro.optim.optimizers import adam

    cfg = get_config(args.arch, reduced=args.reduced)
    mesh = make_smoke_mesh()
    shape = InputShape("cli", args.seq, args.batch, "train")
    bundle = api.build(cfg, mesh, shape)
    mod = api._mod(cfg)
    key = jax.random.key(args.seed)
    params = mod.init_params(cfg, bundle.ctx, key)
    opt = adam(cfg.lr)
    opt_state = opt.init(params)

    def batch_for(k):
        b = {"tokens": jax.random.randint(k, (args.batch, args.seq), 0,
                                          cfg.vocab_size)}
        b["labels"] = b["tokens"]
        if cfg.family == "encdec":
            b["enc_embeds"] = jax.random.normal(
                k, (args.batch, args.seq, cfg.d_model), jnp.bfloat16)
        if cfg.modality == "vision":
            b["patch_embeds"] = jax.random.normal(
                k, (args.batch, args.seq // 8, cfg.d_model), jnp.bfloat16)
        return b

    for step in range(args.steps):
        key, k = jax.random.split(key)
        params, opt_state, m = bundle.fn(params, opt_state, batch_for(k))
        if step % max(args.steps // 10, 1) == 0 or step == args.steps - 1:
            print(f"step {step:4d} loss {float(m['loss']):.4f} "
                  f"gnorm {float(m['gnorm']):.3f}", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--task", choices=["mbrl", "lm"], default="mbrl")
    # mbrl
    ap.add_argument("--env", default="pendulum")
    ap.add_argument("--algo", default="me-trpo",
                    choices=["me-trpo", "me-ppo", "mb-mpo"])
    ap.add_argument("--engine", default="async",
                    choices=["async", "sequential", "partial-model",
                             "partial-data"])
    ap.add_argument("--mode", default="event",
                    choices=["event", "threads", "procs"],
                    help="async engine execution: simulated (event), "
                         "host threads, or separate OS processes with "
                         "shared-memory parameter stores (procs)")
    ap.add_argument("--trajs", type=int, default=40)
    ap.add_argument("--n-models", type=int, default=5)
    ap.add_argument("--model-hidden", type=int, default=128)
    ap.add_argument("--policy-hidden", type=int, default=64)
    ap.add_argument("--imagine-batch", type=int, default=64)
    ap.add_argument("--imagine-horizon", type=int, default=40)
    ap.add_argument("--collect-speed", type=float, default=1.0)
    ap.add_argument("--n-collectors", type=int, default=1,
                    help="size of the data-collection fleet (async "
                         "engine, all modes): N parallel collectors "
                         "share the one global --trajs criterion")
    ap.add_argument("--collect-noise", default=None,
                    help="comma-separated per-collector exploration "
                         "noise scales, cycled across the fleet "
                         "(default: 1.0 everywhere)")
    ap.add_argument("--envs-per-collector", type=int, default=1,
                    help="env farm (async engine, all modes): each "
                         "collector simulates B envs per step through "
                         "one vmapped rollout and pushes the whole "
                         "batch at once (1 = classic single-rollout "
                         "collector)")
    ap.add_argument("--ema-weight", type=float, default=0.9)
    ap.add_argument("--no-early-stop", action="store_true")
    ap.add_argument("--mesh", default="none",
                    help="none | auto | <device count>: role-shard the "
                         "async engine over a device mesh (core/roles.py)")
    ap.add_argument("--role-ratios", default="1,2,1",
                    help="collector,model,policy share of the mesh axis")
    ap.add_argument("--transport", default="shm", choices=["shm", "tcp"],
                    help="how workers reach the servers: shm = in-process"
                         " / shared-memory fast path (default); tcp = "
                         "socket control plane (net/), reachable from "
                         "other hosts via --bind")
    ap.add_argument("--bind", default=None,
                    help="tcp transport: HOST:PORT the control plane "
                         "listens on (default 127.0.0.1:<ephemeral>); "
                         "bind :PORT or 0.0.0.0:PORT to let remote "
                         "collectors --connect")
    ap.add_argument("--connect", default=None,
                    help="join a LIVE run as extra remote collectors "
                         "instead of training: HOST:PORT of its control "
                         "plane (pair with --n-collectors for fan-out). "
                         "Connect only to planes you trust — the join "
                         "ticket is a pickle (docs/WIRE_PROTOCOL.md)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="procs mode: where the supervisor snapshots "
                         "params+versions (default: fresh temp dir)")
    ap.add_argument("--out", default=None)
    ap.add_argument("--profile-dir", default=None,
                    help="record a profiler trace of the run into DIR "
                         "(jax.profiler.trace): the engine's spans on "
                         "the device's clock; threads and event modes")
    # lm
    ap.add_argument("--arch", default="glm4-9b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.task == "mbrl":
        if args.connect:
            run_join(args)
            return
        run_mbrl(args)
    else:
        run_lm(args)


if __name__ == "__main__":
    main()
