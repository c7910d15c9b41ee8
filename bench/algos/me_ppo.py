"""ME-PPO (arXiv:1910.12453, §5): one clipped-surrogate Adam step on
imagined rollouts per policy improvement.

The four functions the harness finds an algorithm by
(``harness/cells.algorithm``): the reference's policy-learner state and
policy step, the leaves the check compares for the first step, and the
operations of one step.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from harness import check, flops, reference


def init(pol, c):
    return {"policy": pol, "opt": reference.adam_init(pol)}


def step(state, model, key, c, mm, fault):
    obs, pre, adv, weights, ret = reference.policy_batch(
        state, model, key, c, mm, fault)
    new, opt = ppo(state["policy"], state["opt"], obs, pre, adv, weights,
                   c, mm)
    return {**state, "policy": new, "opt": opt}, ret


def first_step(state0, state1):
    """Adam's first moment after the first step: the gradient as the
    update takes it (the first step's change is Adam's normalised sign,
    nearly the same on any gradient)."""
    opt = state1["opt"]
    return check.leaves(opt["m"] if isinstance(opt, dict) else opt.mu)


def step_ops(c) -> int:
    """Imagination, then the old log-probabilities and one gradient (4
    policy passes)."""
    return (flops.imagination(c)
            + flops.policy_pass(c) * 4 * flops.imagine_rows(c))


def ppo(pol, opt, obs, pre, adv, weights, c, mm):
    """One clipped-surrogate (0.2) Adam step from the pre-step policy."""
    lp0 = reference.log_prob(pol, obs, pre, mm)

    def loss(p):
        ratio = jnp.exp(reference.log_prob(p, obs, pre, mm) - lp0)
        per = jnp.minimum(ratio * adv, jnp.clip(ratio, 0.8, 1.2) * adv)
        return -jnp.sum(per * weights) / jnp.maximum(weights.sum(), 1.0)

    g = jax.grad(loss)(pol)
    return reference.adam_update(pol, g, opt, c["ppo_lr"])
