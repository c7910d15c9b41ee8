"""MB-MPO (Clavera et al. 2018, arXiv:1809.05214), run asynchronously as
in arXiv:1910.12453 (§5): one meta-policy step on imagined rollouts per
policy improvement.

For each of the K ensemble members m, the policy theta is adapted by one
VPG step of ``inner_lr`` on a rollout imagined from member m alone,
giving theta'_m; a second rollout is imagined from member m with
theta'_m. One Adam step of ``ppo_lr`` then descends the members' mean
outer loss, differentiated through the inner step. Each loss is VPG's
likelihood-ratio surrogate on its own samples, held fixed, and the
meta-gradient flows only through theta'_m(theta) = theta - inner_lr *
grad L_in(theta): MAML's estimator, which MB-MPO uses.

The four functions the harness finds an algorithm by
(``harness/cells.algorithm``): the reference's policy-learner state and
policy step, the leaves the check compares for the first step, and the
operations of one step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from harness import check, flops, reference


def init(pol, c):
    return {"policy": pol, "opt": reference.adam_init(pol)}


def step(state, model, key, c, mm, fault):
    """Member m draws its inner and outer rollouts from the two halves of
    the m-th of K keys split from ``key``, from the ensemble's m-th member
    alone; every loss mean is over the rows' weights (half of them under
    the half-batch fault)."""
    pol, K, lr = state["policy"], c["n_models"], c["inner_lr"]
    one = {**c, "n_models": 1}
    grads, rets = [], []
    for m, k in enumerate(jax.random.split(key, K)):
        k_in, k_out = jax.random.split(k)
        member = {"members": jax.tree.map(lambda x: x[m:m + 1],
                                          model["members"]),
                  "norm": model["norm"]}
        inner = reference.policy_batch({"policy": pol}, member, k_in, one,
                                       mm, fault)[:4]
        adapted = _adapt(pol, inner, lr, mm)
        *outer, ret = reference.policy_batch({"policy": adapted}, member,
                                             k_out, one, mm, fault)
        grads.append(_meta_grad(pol, inner, tuple(outer), lr, mm))
        rets.append(ret)
    g = jax.tree.map(lambda *x: sum(x) / K, *grads)
    new, opt = reference.adam_update(pol, g, state["opt"], c["ppo_lr"])
    return {**state, "policy": new, "opt": opt}, sum(rets) / K


def first_step(state0, state1):
    """Adam's first moment after the first step: the meta-gradient as the
    update takes it."""
    opt = state1["opt"]
    return check.leaves(opt["m"] if isinstance(opt, dict) else opt.mu)


def step_ops(c) -> int:
    """2 K imaginations (K inner, K outer), then per member: the inner
    gradient on the inner rows (3 policy passes: forward, backward to the
    activations, backward to the weights), the outer gradient on the
    outer rows (3), and the meta-gradient's backward through the inner
    gradient on the inner rows, a Hessian-vector product (6: each of the
    inner gradient's 3 products differentiated in both operands). Each
    pass is counted once: the forward of the inner gradient is not
    recomputed for the meta-gradient."""
    K, rows = c["n_models"], flops.imagine_rows(c)
    return (2 * K * flops.imagination(c)
            + K * flops.policy_pass(c) * (3 + 3 + 6) * rows)


def surrogate(p, batch, mm):
    """VPG's likelihood-ratio surrogate on samples held fixed: minus the
    weighted mean of log-probability times advantage."""
    obs, pre, adv, weights = batch
    lp = reference.log_prob(p, obs, pre, mm)
    return -jnp.sum(lp * adv * weights) / jnp.maximum(weights.sum(), 1.0)


def adapted(theta, inner, lr, mm):
    """theta': one VPG step of ``lr`` on the inner batch."""
    g = jax.grad(surrogate)(theta, inner, mm)
    return jax.tree.map(lambda p, gg: p - lr * gg, theta, g)


_adapt = jax.jit(adapted, static_argnums=(2, 3))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _meta_grad(theta, inner, outer, lr, mm):
    """d/dtheta of the outer surrogate at theta'(theta), the samples of
    both rollouts held fixed."""
    return jax.grad(lambda th: surrogate(adapted(th, inner, lr, mm), outer,
                                         mm))(theta)
