"""Model-based algorithm 'policy improvement steps' (Alg. 3, Step op).

Each algorithm exposes::

  init(key)                                   -> algo_state
  improve(algo_state, model_params, key)      -> (algo_state, info)

where ``improve`` is the MINIMAL unit of work the paper assigns to the
policy-improvement worker: sample a batch of imaginary trajectories from
the current dynamics model and take ONE policy-gradient (TRPO/PPO) step.

* ME-TRPO  [10]: imagined rollouts from the ensemble -> TRPO step.
* ME-PPO   [paper §5.1]: same, PPO clipped step.
* MB-MPO   [4]: per-model inner VPG adaptation, outer PPO step on the
  post-adaptation surrogate (meta-policy optimization).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.kernels.mesh import on_mesh
from repro.mbrl import dynamics as DYN
from repro.mbrl import policy as PI
from repro.mbrl import ppo as PPO
from repro.mbrl import trpo as TRPO
from repro.optim.optimizers import adam, apply_updates


@dataclasses.dataclass(frozen=True)
class AlgoConfig:
    algo: str = "me-trpo"           # me-trpo | me-ppo | mb-mpo
    imagine_batch: int = 64         # parallel imagined starts
    imagine_horizon: int = 50
    gamma: float = 0.99
    max_kl: float = 0.01
    ppo_lr: float = 3e-4
    inner_lr: float = 0.05          # MB-MPO inner adaptation step size
    n_models: int = 5


def _rollout_with_logp(model_params, pol_params, s0, key, H, reward_fn,
                       predict_fn=None, *, fused=True):
    """Imagined rollout recording pre-tanh actions for exact densities.

    ``predict_fn=None`` is the ensemble fast path: member assignments
    AND policy noise for the whole horizon are drawn up front, and each
    step is ONE fused ``DYN.step_fused`` dispatch — policy head +
    assigned-member dynamics in a single kernel, no K* ensemble
    overcompute and no per-step sort inside the scan. ``fused=False``
    keeps the legacy two-call step (``PI.sample_with_logp`` +
    ``DYN.predict_assigned``) for parity/benchmark comparison. A
    non-None ``predict_fn`` with the ``(params, obs, act, key)``
    contract swaps in any other world model (e.g. ``wm_dynamics``)."""
    if predict_fn is None:
        ka, kp = jax.random.split(key)
        members = DYN.sample_members(model_params, kp, (H, s0.shape[0]))

        if fused:
            act_dim = pol_params["w"][-1].shape[1]
            eps = DYN.hoisted_noise(ka, H, s0.shape[0], act_dim)
            plan = DYN.horizon_plan(model_params, members)

            def step(carry, xs):
                e, midx, pl_ = xs
                s = carry
                s2, a, pre = DYN.step_fused(model_params, pol_params, s,
                                            e, midx, plan=pl_)
                r = reward_fn(s, a, s2)
                return s2, (s, pre, r)

            _, (obs, pre, rew) = jax.lax.scan(
                step, s0, (eps, members, plan))
            return obs, pre, rew

        def step(carry, xs):
            k, midx = xs
            s = carry
            a, pre, lp = PI.sample_with_logp(pol_params, s, k)
            s2 = DYN.predict_assigned(model_params, s, a, midx)
            r = reward_fn(s, a, s2)
            return s2, (s, pre, r)

        _, (obs, pre, rew) = jax.lax.scan(
            step, s0, (jax.random.split(ka, H), members))
        return obs, pre, rew

    def step(carry, k):
        s = carry
        ka, kp = jax.random.split(k)
        a, pre, lp = PI.sample_with_logp(pol_params, s, ka)
        s2 = predict_fn(model_params, s, a, kp)
        r = reward_fn(s, a, s2)
        return s2, (s, pre, r)

    _, (obs, pre, rew) = jax.lax.scan(step, s0, jax.random.split(key, H))
    return obs, pre, rew


def _flat_batch(obs, pre, rew, gamma):
    rtg, adv = TRPO.compute_advantages(rew, gamma=gamma)
    flat = lambda x: x.reshape((-1,) + x.shape[2:])
    return {"obs": flat(obs), "act_pre": flat(pre), "adv": adv.reshape(-1)}


class _MeshMixin:
    """Shared role-mesh hook: ``configure_mesh`` pins imagined-rollout
    batches (and everything downstream: advantages, TRPO statistics) to
    the policy sub-mesh's batch axis. Params stay replicated — the worker
    places them (core/workers.py). Without a mesh, ``_shard_batch`` is
    the identity and the jitted step is unchanged."""

    _batch_sharding = None

    def configure_mesh(self, mesh, batch_axis: str | None = None) -> None:
        from jax.sharding import NamedSharding, PartitionSpec
        axis = batch_axis or mesh.axis_names[0]
        self._batch_sharding = NamedSharding(mesh, PartitionSpec(axis))
        # drop any traces compiled before the mesh was known
        self._improve = jax.jit(self._improve_impl)

    def _mesh_scope(self):
        """Trace-time scope: kernels run per shard of the sub-mesh."""
        return on_mesh(None if self._batch_sharding is None
                       else self._batch_sharding.mesh)

    def _shard_batch(self, x):
        if self._batch_sharding is None:
            return x
        return jax.tree.map(
            lambda v: jax.lax.with_sharding_constraint(
                v, self._batch_sharding), x)


class MEAlgo(_MeshMixin):
    """ME-TRPO / ME-PPO policy improvement."""

    def __init__(self, cfg: AlgoConfig, pol_cfg: PI.PolicyConfig, reward_fn,
                 init_state_fn, *, predict_fn=None, mesh=None,
                 batch_axis=None):
        self.cfg = cfg
        self.pol_cfg = pol_cfg
        self.reward_fn = reward_fn
        self.init_state_fn = init_state_fn  # key, n -> (n, obs_dim)
        self.predict_fn = predict_fn        # None = ensemble fast path;
        #                                     swap in a world model here
        if cfg.algo == "me-ppo":
            self._ppo_opt, self._ppo_step = PPO.make_ppo_step(cfg.ppo_lr)
        self._improve = jax.jit(self._improve_impl)
        if mesh is not None:
            self.configure_mesh(mesh, batch_axis)

    def init(self, key):
        pol = PI.init_policy(self.pol_cfg, key)
        state = {"policy": pol, "steps": jnp.zeros((), jnp.int32)}
        if self.cfg.algo == "me-ppo":
            state["opt"] = self._ppo_opt.init(pol)
        return state

    def _improve_impl(self, state, model_params, key):
        cfg = self.cfg
        k0, k1 = jax.random.split(key)
        # shard imagined starts over the policy sub-mesh: the rollout scan
        # carries the batch dim, so imagination runs data-parallel
        s0 = self._shard_batch(self.init_state_fn(k0, cfg.imagine_batch))
        with self._mesh_scope():
            obs, pre, rew = _rollout_with_logp(
                model_params, state["policy"], s0, k1, cfg.imagine_horizon,
                self.reward_fn, self.predict_fn)
        # TRPO/PPO statistics (advantages, Fisher-vector products, line
        # search) computed over the sharded flat batch
        batch = self._shard_batch(_flat_batch(obs, pre, rew, cfg.gamma))
        info = {"imagined_return": rew.sum(0).mean()}
        if cfg.algo == "me-trpo":
            new_pol, tinfo = TRPO.trpo_step(state["policy"], batch,
                                            max_kl=cfg.max_kl)
            info.update(tinfo)
            new_state = {**state, "policy": new_pol,
                         "steps": state["steps"] + 1}
        else:
            new_pol, opt, loss = self._ppo_step(
                state["policy"], state["opt"], state["policy"], batch)
            info["ppo_loss"] = loss
            new_state = {**state, "policy": new_pol, "opt": opt,
                         "steps": state["steps"] + 1}
        return new_state, info

    def improve(self, state, model_params, key):
        return self._improve(state, model_params, key)


class MBMPO(_MeshMixin):
    """MB-MPO [4]: meta-policy optimization over the model ensemble.

    Inner loop: for each ensemble member m, adapt theta with one VPG step
    on imagined data from member m. Outer loop: PPO step on the
    post-adaptation surrogate averaged over members.

    On a role mesh the whole meta-step runs replicated over the policy
    sub-mesh (params placement, core/workers.py); the per-member vmap
    keeps its layout and batches are NOT constrained — constraining
    inside the member vmap would fight the vmapped axis, so
    ``_improve_impl`` simply never calls ``_shard_batch``."""

    def __init__(self, cfg: AlgoConfig, pol_cfg: PI.PolicyConfig, reward_fn,
                 init_state_fn, *, predict_fn=None, mesh=None,
                 batch_axis=None):
        self.cfg = cfg
        self.pol_cfg = pol_cfg
        self.reward_fn = reward_fn
        self.init_state_fn = init_state_fn
        self.predict_fn = predict_fn        # None = ensemble fast path
        self._outer_opt = adam(cfg.ppo_lr)
        self._improve = jax.jit(self._improve_impl)
        if mesh is not None:
            self.configure_mesh(mesh, batch_axis)

    def init(self, key):
        pol = PI.init_policy(self.pol_cfg, key)
        return {"policy": pol, "opt": self._outer_opt.init(pol),
                "steps": jnp.zeros((), jnp.int32)}

    def _member_params(self, model_params, m):
        if "members" not in model_params:
            # non-ensemble world model (predict_fn swap): every inner
            # loop adapts against the same model
            return model_params
        members = jax.tree.map(
            lambda x: jax.lax.dynamic_slice_in_dim(x, m, 1, axis=0),
            model_params["members"])
        return {"members": members, "norm": model_params["norm"]}

    def _vpg_loss(self, pol, member, s0, key):
        obs, pre, rew = _rollout_with_logp(member, pol, s0, key,
                                           self.cfg.imagine_horizon,
                                           self.reward_fn, self.predict_fn)
        batch = _flat_batch(obs, pre, rew, self.cfg.gamma)
        lp = PI.log_prob(pol, batch["obs"], batch["act_pre"])
        return -(lp * batch["adv"]).mean(), rew.sum(0).mean()

    def _improve_impl(self, state, model_params, key):
        cfg = self.cfg
        pol = state["policy"]
        K = cfg.n_models

        def meta_loss(theta, key):
            def per_member(m, k):
                member = self._member_params(model_params, m)
                k_in, k_out = jax.random.split(k)
                # independent keys for start-state draws and rollout
                # sampling (reusing k_in for both correlates the inner
                # rollout's action noise with the start states)
                k_s0_in, k_roll_in = jax.random.split(k_in)
                s0 = self.init_state_fn(k_s0_in, cfg.imagine_batch)
                (l_in, _), g = jax.value_and_grad(
                    self._vpg_loss, has_aux=True)(theta, member, s0,
                                                  k_roll_in)
                adapted = jax.tree.map(lambda p, gg: p - cfg.inner_lr * gg,
                                       theta, g)
                k_s0_out, k_roll_out = jax.random.split(k_out)
                s1 = self.init_state_fn(k_s0_out, cfg.imagine_batch)
                l_out, ret = self._vpg_loss(adapted, member, s1, k_roll_out)
                return l_out, ret

            keys = jax.random.split(key, K)
            losses, rets = jax.vmap(per_member)(jnp.arange(K), keys)
            return losses.mean(), rets.mean()

        (loss, ret), g = jax.value_and_grad(meta_loss, has_aux=True)(pol, key)
        upd, opt = self._outer_opt.update(g, state["opt"], pol)
        new_pol = apply_updates(pol, upd)
        info = {"meta_loss": loss, "imagined_return": ret}
        return ({"policy": new_pol, "opt": opt,
                 "steps": state["steps"] + 1}, info)

    def improve(self, state, model_params, key):
        return self._improve(state, model_params, key)


def make_algo(cfg: AlgoConfig, pol_cfg: PI.PolicyConfig, reward_fn,
              init_state_fn, *, predict_fn=None, mesh=None,
              batch_axis=None):
    """``predict_fn=None`` -> ensemble sample-then-compute fast path;
    any ``(params, obs, act, key)`` callable swaps the world model for
    every algorithm (ME-* and MB-MPO alike). ``mesh``: policy role
    sub-mesh (core/roles.py) to shard imagination/TRPO batches over —
    usually left None and configured by the engine via
    ``algo.configure_mesh``."""
    if cfg.algo in ("me-trpo", "me-ppo"):
        return MEAlgo(cfg, pol_cfg, reward_fn, init_state_fn,
                      predict_fn=predict_fn, mesh=mesh,
                      batch_axis=batch_axis)
    if cfg.algo == "mb-mpo":
        return MBMPO(cfg, pol_cfg, reward_fn, init_state_fn,
                     predict_fn=predict_fn, mesh=mesh,
                     batch_axis=batch_axis)
    raise ValueError(cfg.algo)
