"""MBRL substrate tests: envs, dynamics ensemble, TRPO/PPO, algorithms."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.envs import make_env
from repro.mbrl import dynamics as DYN
from repro.mbrl import policy as PI
from repro.mbrl import ppo as PPO
from repro.mbrl import trpo as TRPO
from repro.mbrl.algos import AlgoConfig, make_algo
from repro.mbrl.policy import PolicyConfig

ENVS = ["pendulum", "cartpole_swingup", "spring_hopper", "reacher2",
        "pr2_reach"]


@pytest.mark.parametrize("name", ENVS)
def test_env_rollout_finite(name):
    env = make_env(name)
    pol = PI.init_policy(PolicyConfig(env.obs_dim, env.act_dim, hidden=8),
                         jax.random.key(0))
    tr = jax.jit(lambda k: env.rollout(k, PI.sample_action, pol))(
        jax.random.key(1))
    assert tr["obs"].shape == (env.horizon, env.obs_dim)
    for k, v in tr.items():
        assert jnp.isfinite(v).all(), k


@pytest.mark.parametrize("name", ENVS)
def test_env_reward_consistency(name):
    """step()'s returned reward equals reward(s, a, s') — required for
    imagination to be faithful to the env."""
    env = make_env(name)
    key = jax.random.key(2)
    s = env.reset(key)
    for i in range(5):
        a = jax.random.uniform(jax.random.fold_in(key, i), (env.act_dim,),
                               minval=-1, maxval=1)
        s2, r = env.step(s, a)
        r2 = env.reward(s, a, s2)
        np.testing.assert_allclose(float(r), float(r2), rtol=1e-5, atol=1e-5)
        s = s2


def test_ensemble_learns_dynamics():
    """The ensemble must fit a simple known system well."""
    env = make_env("pendulum")
    cfg = DYN.EnsembleConfig(env.obs_dim, env.act_dim, hidden=64,
                             n_models=2, lr=3e-3)
    key = jax.random.key(0)
    params = DYN.init_ensemble(cfg, key)
    pol = PI.init_policy(PolicyConfig(env.obs_dim, env.act_dim, hidden=8),
                         key)
    trajs = [env.rollout(jax.random.fold_in(key, i), PI.sample_action, pol)
             for i in range(8)]
    obs = jnp.concatenate([t["obs"] for t in trajs])
    act = jnp.concatenate([t["act"] for t in trajs])
    nobs = jnp.concatenate([t["next_obs"] for t in trajs])
    params = DYN.update_normalizer(params, obs, act, nobs)
    opt, train_epoch, val_loss = DYN.make_model_trainer(cfg)
    opt_state = opt.init(params)
    l0 = float(val_loss(params, obs, act, nobs))
    for e in range(10):
        params, opt_state, _ = train_epoch(params, opt_state, obs, act,
                                           nobs, jax.random.fold_in(key, e))
    l1 = float(val_loss(params, obs, act, nobs))
    assert l1 < l0 * 0.5, (l0, l1)
    # uniform-prior sampling returns plausible next states
    pred = DYN.predict(params, obs[:16], act[:16], key)
    assert pred.shape == (16, env.obs_dim)
    assert jnp.isfinite(pred).all()


def test_predict_assigned_matches_predict():
    """Sample-then-compute must be a pure reorganisation of the FLOPs:
    under the assignment ``predict`` itself draws, ``predict_assigned``
    returns bit-identical next states (dense select impl on CPU)."""
    env = make_env("pendulum")
    cfg = DYN.EnsembleConfig(env.obs_dim, env.act_dim, hidden=32,
                             n_models=5)
    key = jax.random.key(7)
    params = DYN.init_ensemble(cfg, key)
    obs = jax.random.normal(jax.random.fold_in(key, 1), (24, env.obs_dim))
    act = jax.random.uniform(jax.random.fold_in(key, 2),
                             (24, env.act_dim), minval=-1, maxval=1)
    legacy = DYN.predict(params, obs, act, key)
    idx = DYN.sample_members(params, key, (obs.shape[0],))
    assigned = DYN.predict_assigned(params, obs, act, idx)
    np.testing.assert_array_equal(np.asarray(legacy), np.asarray(assigned))
    # and the sort/ragged/unsort ref path agrees numerically
    from repro.kernels.gmm import ops as gmm_ops
    n = params["norm"]
    xn = (jnp.concatenate([obs, act], -1) - n["mu_in"]) / n["sig_in"]
    dyn = gmm_ops.ensemble_mlp_select(params["members"], xn, idx,
                                      impl="ref")
    ragged = obs + dyn * n["sig_out"] + n["mu_out"]
    np.testing.assert_allclose(np.asarray(ragged), np.asarray(legacy),
                               atol=1e-5, rtol=1e-5)


def test_imagine_rollout_uses_every_member_and_is_finite():
    env = make_env("pendulum")
    cfg = DYN.EnsembleConfig(env.obs_dim, env.act_dim, hidden=16,
                             n_models=3)
    key = jax.random.key(9)
    params = DYN.init_ensemble(cfg, key)
    pol = PI.init_policy(PolicyConfig(env.obs_dim, env.act_dim, hidden=8),
                         key)
    s0 = env.reset_batch(key, 16)
    traj = jax.jit(lambda p, pp, s, k: DYN.imagine_rollout(
        p, PI.sample_action, pp, s, k, 12, jax.vmap(env.reward)))(
        params, pol, s0, key)
    assert traj["obs"].shape == (12, 16, env.obs_dim)
    for k, v in traj.items():
        assert jnp.isfinite(v).all(), k


def test_trpo_improves_surrogate_and_respects_kl():
    env = make_env("pendulum")
    key = jax.random.key(3)
    pol = PI.init_policy(PolicyConfig(env.obs_dim, env.act_dim, hidden=16),
                         key)
    obs = jax.random.normal(key, (256, env.obs_dim))
    act, pre, lp = PI.sample_with_logp(pol, obs, key)
    adv = jax.random.normal(jax.random.fold_in(key, 1), (256,))
    batch = {"obs": obs, "act_pre": pre, "adv": adv}
    new_pol, info = jax.jit(lambda p, b: TRPO.trpo_step(p, b))(pol, batch)
    kl = float(PI.kl_divergence(pol, new_pol, obs))
    assert kl <= 0.02, kl
    s_new = float(TRPO.surrogate(new_pol, pol, batch))
    assert s_new >= 0.0                      # line search demanded improvement


def test_ppo_step_reduces_loss():
    env = make_env("pendulum")
    key = jax.random.key(4)
    pol = PI.init_policy(PolicyConfig(env.obs_dim, env.act_dim, hidden=16),
                         key)
    obs = jax.random.normal(key, (128, env.obs_dim))
    _, pre, _ = PI.sample_with_logp(pol, obs, key)
    adv = jax.random.normal(jax.random.fold_in(key, 2), (128,))
    batch = {"obs": obs, "act_pre": pre, "adv": adv}
    opt, step = PPO.make_ppo_step(lr=1e-3)
    st = opt.init(pol)
    old = jax.tree.map(lambda x: x, pol)
    l0 = float(PPO.ppo_loss(pol, old, batch))
    p, st, _ = step(pol, st, old, batch)
    for _ in range(5):
        p, st, _ = step(p, st, old, batch)
    l1 = float(PPO.ppo_loss(p, old, batch))
    assert l1 < l0


@pytest.mark.parametrize("algo", ["me-trpo", "me-ppo", "mb-mpo"])
def test_algos_one_improve_step(algo):
    env = make_env("pendulum")
    n_models = 2
    ens_cfg = DYN.EnsembleConfig(env.obs_dim, env.act_dim, hidden=32,
                                 n_models=n_models)
    key = jax.random.key(5)
    model_params = DYN.init_ensemble(ens_cfg, key)
    acfg = AlgoConfig(algo=algo, imagine_batch=8, imagine_horizon=10,
                      n_models=n_models)
    a = make_algo(acfg, PolicyConfig(env.obs_dim, env.act_dim, hidden=16),
                  jax.vmap(env.reward), env.reset_batch)
    state = a.init(key)
    state2, info = a.improve(state, model_params, key)
    assert int(state2["steps"]) == 1
    assert jnp.isfinite(info["imagined_return"])
    # params actually changed
    diffs = [float(jnp.abs(x - y).max()) for x, y in
             zip(jax.tree.leaves(state["policy"]),
                 jax.tree.leaves(state2["policy"]))]
    assert max(diffs) > 0


def test_advantage_computation():
    rews = jnp.ones((5, 3))
    rtg, adv = TRPO.compute_advantages(rews, gamma=0.5)
    np.testing.assert_allclose(np.asarray(rtg[:, 0]),
                               [1.9375, 1.875, 1.75, 1.5, 1.0], rtol=1e-5)
    assert abs(float(adv.mean())) < 1e-5


# ------------------------------------------------------------------ MB-MPO
# MB-MPO's estimator is MAML's: every rollout samples from parameters held
# fixed, each loss is VPG's likelihood-ratio surrogate on its own samples,
# and the meta-gradient flows only through theta'_m = theta - a * grad L_in.
# The tests below rebuild each member's rollouts as the program draws them
# and do the calculus in plain jnp.

def _mbmpo(inner_lr):
    env = make_env("pendulum")
    ens_cfg = DYN.EnsembleConfig(env.obs_dim, env.act_dim, hidden=32,
                                 n_models=2)
    key = jax.random.key(5)
    model = DYN.init_ensemble(ens_cfg, key)
    acfg = AlgoConfig(algo="mb-mpo", imagine_batch=32, imagine_horizon=6,
                      n_models=2, inner_lr=inner_lr)
    algo = make_algo(acfg, PolicyConfig(env.obs_dim, env.act_dim, hidden=16),
                     jax.vmap(env.reward), env.reset_batch)
    return env, algo, model, algo.init(jax.random.fold_in(key, 1))


def _member_batch(env, algo, model, pol, m, key):
    """Member m's rollout from ``pol``, drawn as the program draws it
    (starts from the first half of ``key``, sampling from the second),
    flattened to observations, pre-tanh actions and advantages."""
    from repro.mbrl import algos
    cfg = algo.cfg
    member = {"members": jax.tree.map(lambda x: x[m:m + 1],
                                      model["members"]),
              "norm": model["norm"]}
    k_s0, k_roll = jax.random.split(key)
    obs, pre, rew = algos._rollout_with_logp(
        member, pol, env.reset_batch(k_s0, cfg.imagine_batch), k_roll,
        cfg.imagine_horizon, jax.vmap(env.reward))
    _, adv = TRPO.compute_advantages(rew, gamma=cfg.gamma)
    return (obs.reshape(-1, obs.shape[-1]), pre.reshape(-1, pre.shape[-1]),
            adv.reshape(-1))


def _vpg(pol, obs, pre, adv):
    """VPG's surrogate, written out: minus the mean of the Gaussian
    log-density of each pre-tanh action under the tanh-MLP mean, times
    its advantage."""
    h = obs
    for i, (w, b) in enumerate(zip(pol["w"], pol["b"])):
        h = h @ w + b
        if i < len(pol["w"]) - 1:
            h = jnp.tanh(h)
    z = (pre - h) / jnp.exp(pol["log_std"])
    lp = (-0.5 * z ** 2 - pol["log_std"] - 0.5 * jnp.log(2 * jnp.pi)).sum(-1)
    return -(lp * adv).mean()


def _member_keys(key, n_models):
    return [jax.random.split(k) for k in jax.random.split(key, n_models)]


def test_mbmpo_inner_step_is_vpg_on_its_own_rollout():
    lr = 0.05
    env, algo, model, state = _mbmpo(lr)
    theta = state["policy"]
    key = jax.random.key(21)
    _, adapted, _ = algo.meta_gradient(theta, model, key)
    for m, (k_in, _) in enumerate(_member_keys(key, 2)):
        g = jax.grad(_vpg)(theta, *_member_batch(env, algo, model, theta, m,
                                                 k_in))
        for t, a, gg in zip(jax.tree.leaves(theta), jax.tree.leaves(adapted),
                            jax.tree.leaves(g)):
            # theta - theta'_m carries theta's own rounding (|theta| <= 2,
            # an ulp 2.4e-7); the two gradients are the same float32 sums
            # in another order, a few ulps of the gradient apart
            np.testing.assert_allclose(np.asarray(t - a[m]),
                                       np.asarray(lr * gg),
                                       rtol=1e-4, atol=1e-6)


def test_mbmpo_meta_gradient_is_through_the_inner_step():
    lr = 0.5        # large enough that the second-order term shows
    env, algo, model, state = _mbmpo(lr)
    theta = state["policy"]
    key = jax.random.key(22)
    new, _ = algo.improve(state, model, key)
    # Adam's first moment after one step from zero is (1 - b1) * g
    got = jax.tree.map(lambda mu: mu / 0.1, new["opt"].mu)
    want = jax.tree.map(jnp.zeros_like, theta)
    first = jax.tree.map(jnp.zeros_like, theta)
    for m, (k_in, k_out) in enumerate(_member_keys(key, 2)):
        inner = _member_batch(env, algo, model, theta, m, k_in)
        grad_in = lambda th: jax.grad(_vpg)(th, *inner)
        adapted = jax.tree.map(lambda t, g: t - lr * g, theta, grad_in(theta))
        v = jax.grad(_vpg)(adapted, *_member_batch(env, algo, model, adapted,
                                                   m, k_out))
        hv = jax.jvp(grad_in, (theta,), (v,))[1]
        # (I - lr * H_in) grad L_out(theta'_m), averaged over members
        want = jax.tree.map(lambda w, a, b: w + (a - lr * b) / 2, want, v, hv)
        first = jax.tree.map(lambda w, a: w + a / 2, first, v)
    scale = max(float(jnp.abs(x).max()) for x in jax.tree.leaves(want))
    second = max(float(jnp.abs(w - f).max()) for w, f in
                 zip(jax.tree.leaves(want), jax.tree.leaves(first)))
    assert second > 0.05 * scale        # the test tells MAML from FO-MAML
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        # float32 sums in another order, and the test's theta'_m a few
        # ulps off the program's, so its outer rollout too (they agree to
        # about 1e-7 of the largest element): 1e-5 of it, far under the
        # second-order term's share
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-5 * scale)
