"""ME-TRPO (Kurutach et al. 2018, arXiv:1802.10592): one natural-gradient
step on imagined rollouts per policy improvement.

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
    return {"policy": pol}


def step(state, model, key, c, mm, fault):
    obs, pre, adv, weights, ret = reference.policy_batch(
        state, model, key, c, mm, fault)
    return {**state, "policy": trpo(state["policy"], obs, pre, adv,
                                    weights, c, mm)}, ret


def first_step(state0, state1):
    """The change of the first TRPO step."""
    return check.delta(state1["policy"], state0["policy"])


def step_ops(c) -> int:
    """Imagination, then the surrogate's gradient (3 policy passes), the
    old policy's statistics (1), eleven Fisher-vector products on every
    fourth row (a jvp and a vjp, 4 passes each) and ten line-search
    candidates (1 each)."""
    rows = flops.imagine_rows(c)
    stride = max(1, min(4, rows // 256))
    passes = 3 + 1 + 10
    return flops.imagination(c) + flops.policy_pass(c) * (
        passes * rows + 11 * 4 * (rows // stride))


def tdot(a, b):
    return sum(jnp.sum(x * y) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def trpo(pol, obs, pre, adv, weights, c, mm):
    """Natural-gradient step: ten conjugate-gradient iterations on the
    Gauss-Newton Fisher of every fourth row (damping 1e-2), scaled to the
    KL radius, then the first of ten backtracking fractions 0.8**i whose
    KL stays within 1.5x the radius and whose surrogate is positive."""
    cands, kls, surrs = _trpo_candidates(pol, obs, pre, adv, weights,
                                         c["max_kl"], mm)
    for i in range(len(kls)):
        if kls[i] <= c["max_kl"] * 1.5 and surrs[i] > 0:
            return jax.tree.map(lambda x: x[i], cands)
    return pol


@functools.partial(jax.jit, static_argnums=(5, 6))
def _trpo_candidates(pol, obs, pre, adv, weights, max_kl, mm):
    mean = lambda p, o: reference.policy_mean(p, o, mm)
    mu0, ls0 = mean(pol, obs), pol["log_std"]
    v0 = jnp.exp(2 * ls0)
    lp0 = reference.log_prob(pol, obs, pre, mm)
    wsum = jnp.maximum(weights.sum(), 1.0)

    def surr(p):
        return jnp.sum(jnp.exp(reference.log_prob(p, obs, pre, mm) - lp0)
                       * adv * weights) / wsum

    def kl(p):
        mu1, ls1 = mean(p, obs), p["log_std"]
        v1 = jnp.exp(2 * ls1)
        per = (ls1 - ls0 + (v0 + (mu0 - mu1) ** 2) / (2 * v1) - 0.5).sum(-1)
        return jnp.sum(per * weights) / wsum

    g = jax.grad(surr)(pol)
    stride = max(1, min(4, obs.shape[0] // 256))
    ofv = obs[::stride]
    nf = ofv.shape[0]
    _, vjp = jax.vjp(lambda p: mean(p, ofv), pol)

    def fvp(v):
        jv = jax.jvp(lambda p: mean(p, ofv), (pol,), (v,))[1]
        out = vjp(jv / v0 / nf)[0]
        return {**out, "log_std": out["log_std"] + 2.0 * v["log_std"]}

    add = lambda a, b, s=1.0: jax.tree.map(lambda x, y: x + s * y, a, b)
    x = jax.tree.map(jnp.zeros_like, g)
    r = p = g
    rs = tdot(r, r)
    for _ in range(10):
        hp = add(fvp(p), p, 1e-2)
        alpha = rs / (tdot(p, hp) + 1e-10)
        x = add(x, p, alpha)
        r = add(r, hp, -alpha)
        rs_new = tdot(r, r)
        p = add(r, p, rs_new / (rs + 1e-10))
        rs = rs_new
    lm = jnp.sqrt(jnp.maximum(tdot(x, fvp(x)), 1e-10) / (2 * max_kl))
    full = jax.tree.map(lambda v: v / jnp.maximum(lm, 1e-10), x)
    fracs = 0.8 ** jnp.arange(10, dtype=jnp.float32)
    cands = jax.vmap(lambda f: add(pol, full, f))(fracs)
    kls, surrs = jax.vmap(lambda cand: (kl(cand), surr(cand)))(cands)
    return cands, kls, surrs
