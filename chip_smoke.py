#!/usr/bin/env python3
"""Bring-up smoke run of the asynchronous ME-TRPO trainer on a TPU chip.

    python3 chip_smoke.py                 # one chip
    python3 chip_smoke.py --four-chips    # role-sharded engine, 4 chips

One process holds the chip for the whole run; phases, in order:

1. device — ``jax.devices()[0].platform`` must be ``tpu``. There is no
   CPU fallback: without a chip the script exits non-zero and prints no
   result.
2. kernel parity, on the chip — ``imag.fused_step``, ``gmm.ensemble_mlp``
   and ``gmm.ensemble_mlp_select`` through Pallas (compiled, not
   interpreted) against the pure-jnp oracle, and ``jax.grad`` of the
   model loss through the Pallas path against the oracle's gradient.
   The oracle runs at ``highest`` matmul precision: the kernels contract
   in f32 on the MXU, XLA's default would round operands to bf16.
3. trainer — ``AsyncTrainer(mode="threads")``, built the way
   ``python -m repro.launch.train --task mbrl`` builds it, runs to its
   criterion; it must land ``total_trajs`` exactly, with model and policy
   versions of at least 3 and a finite final eval return.

``--four-chips`` replaces phases 2-3 with the role-sharded engine on a
``(4,)`` mesh split (1, 2, 1): a sharded ``train_epoch`` and
``imagine_rollout`` against the same calls on one chip, then the same
trainer on the mesh.

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``; any failed
phase raises before it is printed. Weights and data come from ``--seed``.
"""
from __future__ import annotations

import argparse
import dataclasses
import faulthandler
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
PLATFORM = "tpu"            # the only platform this script accepts
INTERPRET = False           # Pallas kernels compiled for the chip
WATCHDOG_S = 1100           # dump stacks and exit(1) if still running

# Relative error = max|got - oracle| / max|oracle| per tensor. f32
# products accumulated in a different order differ by ~1e-6 of the
# scale; one bf16 pass over a 512-wide contraction errs by ~3e-3. 1e-4
# admits the first with 100x room and rejects the second by 30x.
REL_TOL = 1e-4


@dataclasses.dataclass(frozen=True)
class Widths:
    """The smoke run's configuration (full widths, cut in run length)."""
    env: str = "pr2_reach"            # Arm7: obs 23, act 7
    n_models: int = 5
    model_hidden: int = 512
    policy_hidden: int = 64
    imagine_batch: int = 4096
    imagine_horizon: int = 40
    envs_per_collector: int = 64
    total_trajs: int = 640            # ten farm steps of 64 robots
    min_versions: int = 3


SOURCE = ("dynamics: K=5 MLPs with 2 hidden layers of 512 units, the "
          "setting of MB-MPO (Clavera et al. 2018, arXiv:1809.05214) on "
          "which this paper's ME-TRPO/MB-MPO setups build; tanh hidden "
          "activations (this repo's ensemble); policy 2x64; imagination "
          "4096 rows x horizon 40")


def log(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------ device
def device_phase(expect_count: int):
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != PLATFORM:
        raise SystemExit(f"no {PLATFORM} device: jax found {d.platform!r} "
                         f"({len(devs)} device(s)); nothing was run")
    if len(devs) < expect_count:
        raise SystemExit(f"need {expect_count} chips, found {len(devs)}")
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}
    log(f"[device] {json.dumps(info)}")
    return info


# ------------------------------------------------------------ parity
def _errors(got, exp):
    import jax
    import numpy as np
    abs_err, rel_err = 0.0, 0.0
    for g, e in zip(jax.tree.leaves(got), jax.tree.leaves(exp)):
        g = np.asarray(g, np.float64)
        e = np.asarray(e, np.float64)
        if not np.isfinite(g).all():
            return math.inf, math.inf
        a = float(np.abs(g - e).max())
        abs_err = max(abs_err, a)
        rel_err = max(rel_err, a / max(float(np.abs(e).max()), 1e-30))
    return abs_err, rel_err


def _inputs(w: Widths, seed: int):
    import jax
    import jax.numpy as jnp

    from repro.envs import make_env
    from repro.mbrl import dynamics as DYN
    from repro.mbrl import policy as PI
    env = make_env(w.env)
    key = jax.random.key(seed)
    k = lambda i: jax.random.fold_in(key, i)
    ens = DYN.init_ensemble(DYN.EnsembleConfig(
        env.obs_dim, env.act_dim, hidden=w.model_hidden,
        n_models=w.n_models), k(0))
    din = env.obs_dim + env.act_dim
    ens["norm"] = {          # non-trivial normaliser: every term matters
        "mu_in": 0.1 * jax.random.normal(k(1), (din,)),
        "sig_in": 0.5 + jnp.abs(jax.random.normal(k(2), (din,))),
        "mu_out": 0.05 * jax.random.normal(k(3), (env.obs_dim,)),
        "sig_out": 0.5 + jnp.abs(jax.random.normal(k(4), (env.obs_dim,)))}
    pol = PI.init_policy(PI.PolicyConfig(env.obs_dim, env.act_dim,
                                         hidden=w.policy_hidden), k(5))
    B = w.imagine_batch
    s = env.reset_batch(k(6), B) + 0.3 * jax.random.normal(
        k(7), (B, env.obs_dim))
    eps = jax.random.normal(k(8), (B, env.act_dim))
    midx = jax.random.randint(k(9), (B,), 0, w.n_models)
    x = jax.random.normal(k(10), (B, din))
    return env, ens, pol, s, eps, midx, x


def parity_phase(w: Widths, seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels.gmm import ops as gmm_ops
    from repro.kernels.gmm import ref as gmm_ref
    from repro.kernels.imag import ops as imag_ops
    from repro.kernels.imag import ref as imag_ref
    from repro.mbrl import dynamics as DYN
    env, ens, pol, s, eps, midx, x = _inputs(w, seed)
    m, nrm = ens["members"], ens["norm"]

    def oracle_mse(params, obs, act, next_obs, weights):
        """DYN.masked_mse_loss spelled on the pure-jnp oracle."""
        n = params["norm"]
        target = (next_obs - obs - n["mu_out"]) / n["sig_out"]
        xn = (jnp.concatenate([obs, act], -1) - n["mu_in"]) / n["sig_in"]
        pred = gmm_ref.ensemble_mlp(params["members"], xn)
        per_row = jnp.mean((pred - target[None]) ** 2, axis=(0, 2))
        wt = weights.astype(per_row.dtype)
        return jnp.sum(per_row * wt) / jnp.maximum(jnp.sum(wt), 1.0)

    rows = min(256, len(s))             # the model learner's minibatch
    obs, act = s[:rows], eps[:rows]
    nxt = obs + 0.1 * jnp.tanh(x[:rows, :env.obs_dim])
    wts = jnp.arange(rows) < rows - 17  # a masked ring tail
    # name: (kernel path, oracle, arguments). Arrays go in as arguments,
    # as the learners pass them: on the chip the imag oracle compiled
    # with its inputs as embedded constants disagreed with itself given
    # the same inputs as arguments (PERF.md, PR 11).
    checks = {
        "imag.fused_step": (
            lambda *a: imag_ops.fused_step(*a, impl="pallas",
                                           interpret=INTERPRET),
            imag_ref.fused_step, (m, nrm, pol, s, eps, midx)),
        "gmm.ensemble_mlp": (
            lambda *a: gmm_ops.ensemble_mlp(*a, impl="pallas",
                                            interpret=INTERPRET),
            gmm_ref.ensemble_mlp, (m, x)),
        "gmm.ensemble_mlp_select": (
            lambda *a: gmm_ops.ensemble_mlp_select(*a, impl="pallas",
                                                   interpret=INTERPRET),
            gmm_ref.ensemble_mlp_select, (m, x, midx)),
        # the model learner's gradient: DYN.masked_mse_loss dispatches to
        # the Pallas kernel on the chip, through its custom_vjp
        "grad(masked_mse_loss)": (
            jax.grad(DYN.masked_mse_loss), jax.grad(oracle_mse),
            (ens, obs, act, nxt, wts)),
    }
    log(f"[parity] tolerance: relative error <= {REL_TOL:g} (max|got-"
        f"oracle| / max|oracle|): f32 reordering is ~1e-6 of scale, a "
        f"bf16 MXU pass ~3e-3; oracle at matmul precision 'highest'")
    failed = []
    for name, (kernel, oracle, args) in checks.items():
        got = jax.jit(kernel)(*args)
        with jax.default_matmul_precision("highest"):
            exp = jax.jit(oracle)(*args)
        a, r = _errors(got, exp)
        ok = r <= REL_TOL
        log(f"[parity] {name}: max_abs={a:.3e} max_rel={r:.3e} "
            f"tol={REL_TOL:g} {'ok' if ok else 'BREACH'}")
        if not ok:
            failed.append(name)
    if failed:
        raise RuntimeError(f"kernel parity breached: {failed}")


# ------------------------------------------------------------ trainer
def build_trainer(w: Widths, seed: int, **trainer_kw):
    """The run ``launch/train.py:run_mbrl`` builds, at the smoke widths."""
    import jax

    from repro.core import AsyncTrainer, RunConfig
    from repro.envs import make_env
    from repro.mbrl import (AlgoConfig, EnsembleConfig, PolicyConfig,
                            make_algo)
    env = make_env(w.env)
    ens = EnsembleConfig(env.obs_dim, env.act_dim, hidden=w.model_hidden,
                         n_models=w.n_models)
    pol = PolicyConfig(env.obs_dim, env.act_dim, hidden=w.policy_hidden)
    acfg = AlgoConfig(algo="me-trpo", imagine_batch=w.imagine_batch,
                      imagine_horizon=w.imagine_horizon,
                      n_models=w.n_models)
    algo = make_algo(acfg, pol, jax.vmap(env.reward), env.reset_batch)
    rc = RunConfig(total_trajs=w.total_trajs, seed=seed,
                   envs_per_collector=w.envs_per_collector,
                   min_final_model_version=w.min_versions,
                   # the policy server's version 1 is its initial push
                   min_final_policy_version=w.min_versions + 1)
    return AsyncTrainer(env, ens, algo, rc, mode="threads", algo_cfg=acfg,
                        pol_cfg=pol, **trainer_kw)


def trainer_phase(w: Widths, seed: int, tag: str = "trainer",
                  **trainer_kw) -> None:
    t0 = time.perf_counter()
    tr = build_trainer(w, seed, **trainer_kw)
    if tr.roles is not None:
        log(f"[{tag}] roles: {json.dumps(tr.roles.describe())}")
    trace = tr.run()
    wall = time.perf_counter() - t0
    pushed = tr.data_server.total_pushed
    mv, pv = tr.model_server.version, tr.policy_server.version
    log(f"[{tag}] wall_s={wall:.1f} (compiles included)")
    log(f"[{tag}] compiles: model_train_epoch="
        f"{tr.model_worker.compile_count()} policy_improve="
        f"{tr.policy_worker.compile_count()} collectors="
        f"{[c.compile_count() for c in tr.collectors]}")
    log(f"[{tag}] trajs={pushed}/{w.total_trajs} model_version={mv} "
        f"(epochs {tr.model_worker.epochs}) policy_version={pv} "
        f"(steps {tr.policy_worker.steps})")
    log(f"[{tag}] last trace row: {json.dumps(trace[-1])}")
    if pushed != w.total_trajs:
        raise RuntimeError(f"criterion missed: {pushed} != {w.total_trajs}")
    if mv < w.min_versions or tr.policy_worker.steps < w.min_versions:
        raise RuntimeError(f"learners did not step: model version {mv}, "
                           f"policy steps {tr.policy_worker.steps}")
    if not math.isfinite(trace[-1]["eval_return"]):
        raise RuntimeError(f"eval return not finite: {trace[-1]}")


# ------------------------------------------------------------ 4 chips
def sharded_parity_phase(w: Widths, seed: int) -> None:
    """Sharded train_epoch / imagine_rollout vs the same calls on one
    chip, in this process (tests/_mesh_impl.py's checks at full width)."""
    import jax
    import numpy as np

    from repro.core.roles import batch_sharded, replicated, split_roles
    from repro.core.servers import ReplayBuffer
    from repro.kernels.mesh import on_mesh
    from repro.launch.mesh import make_mesh
    from repro.mbrl import dynamics as DYN
    from repro.mbrl import policy as PI
    env, ens, pol, s, _eps, _midx, _x = _inputs(w, seed)
    mesh = make_mesh((4,), ("data",))
    roles = split_roles(mesh, ratios=(1, 2, 1))
    one = jax.devices()[0]
    host = lambda t: jax.tree.map(lambda v: np.asarray(
        jax.device_put(v, one)), t)

    cfg = DYN.EnsembleConfig(env.obs_dim, env.act_dim,
                             hidden=w.model_hidden, n_models=w.n_models)
    key = jax.random.key(seed)
    batch = jax.jit(lambda p, k: env.rollout_batch(
        k, PI.sample_action, p, 24))(pol, jax.random.fold_in(key, 100))
    trajs = [{name: v[i] for name, v in batch.items()} for i in range(24)]

    def train(sharding):
        rb = ReplayBuffer(24 * env.horizon, holdout_frac=0.0,
                          sharding=sharding)
        opt, train_epoch, val_loss, update_norm = DYN.make_ring_trainer(
            cfg, rb.capacity, batch_sharding=sharding)
        params = DYN.init_ensemble(cfg, key)
        if sharding is not None:
            params = jax.device_put(params, replicated(sharding.mesh))
        opt_state = opt.init(params)
        rb.extend(trajs)
        losses = []
        for e in range(3):
            data, size = rb.train_view()
            params = {**params, "norm": update_norm(data, size)}
            params, opt_state, loss = train_epoch(
                params, opt_state, data, size, jax.random.fold_in(key, e))
            losses.append(float(loss))
        return host(params), losses

    t0 = time.perf_counter()
    p1, l1 = train(None)
    p2, l2 = train(batch_sharded(roles.model))
    a, r = _errors((p2, l2), (p1, l1))
    log(f"[sharded] train_epoch x3 on the {roles.model.devices.size}-chip "
        f"model sub-mesh vs one chip: losses {l1} vs {l2} max_abs={a:.3e} "
        f"max_rel={r:.3e} ({time.perf_counter() - t0:.1f}s)")
    # reduction order differs (per-device grads + psum): 1e-4 as above
    fails = [] if r <= REL_TOL else ["train_epoch"]

    def rollout(on):
        def fn(mp, pp, s0, k):
            with on_mesh(on):       # the Pallas step runs per shard
                return DYN.imagine_rollout(
                    mp, PI.sample_action, pp, s0, k, w.imagine_horizon,
                    jax.vmap(env.reward))
        return jax.jit(fn)

    t0 = time.perf_counter()
    single = host(rollout(None)(ens, pol, s, jax.random.key(seed + 1)))
    rp = replicated(mesh)
    roll = rollout(mesh)
    sharded = host(roll(jax.device_put(ens, rp), jax.device_put(pol, rp),
                        jax.device_put(s, batch_sharded(mesh)),
                        jax.random.key(seed + 1)))
    a, r = _errors(sharded, single)
    log(f"[sharded] imagine_rollout, batch sharded over 4 chips vs one "
        f"chip: max_abs={a:.3e} max_rel={r:.3e} "
        f"({time.perf_counter() - t0:.1f}s)")
    if r > REL_TOL:
        fails.append("imagine_rollout")
    text = roll.lower(jax.device_put(ens, rp), jax.device_put(pol, rp),
                      jax.device_put(s, batch_sharded(mesh)),
                      jax.random.key(seed + 1)).compile().as_text()
    log(f"[sharded] imagine_rollout program: "
        f"{text.count('tpu_custom_call')} tpu_custom_call, "
        f"{text.count('all-gather')} all-gather")
    if fails:
        raise RuntimeError(f"sharded != single chip: {fails}")


# ------------------------------------------------------------ main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the role-sharded engine on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke.py must run from a checkout of the repository "
              f"(no src/repro next to {Path(__file__).name})",
              file=sys.stderr)
        return 2
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    from repro.utils.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    t0 = time.perf_counter()
    info = device_phase(4 if args.four_chips else 1)
    w = Widths()
    log(f"[config] {json.dumps(dataclasses.asdict(w))}")
    log(f"[config] source: {SOURCE}")
    log(f"[config] seed={args.seed} compile_cache={cache}")
    if args.four_chips:
        from repro.launch.mesh import make_mesh
        sharded_parity_phase(w, args.seed)
        trainer_phase(w, args.seed, tag="trainer-4chip",
                      mesh=make_mesh((4,), ("data",)),
                      role_ratios=(1, 2, 1))
    else:
        parity_phase(w, args.seed)
        trainer_phase(w, args.seed)
    log(f"[done] total_s={time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)
    sys.exit(main())
