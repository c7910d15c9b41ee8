"""CPU interpret-mode parity sweep over the OPS dispatchers (ISSUE 10).

``tests/test_kernels.py`` drives the pallas modules directly; this sweep
goes through each family's ``ops`` dispatcher — the entry point the rest
of the codebase actually calls — pinning ``impl="pallas",
interpret=True`` against ``impl="ref"`` (the pure-jnp oracle) on CPU.
Runs standalone as the CI ``kernels-interpret`` step
(``JAX_PLATFORMS=cpu make test-kernels``) so kernel regressions fail
fast and separately from the full tier-1 wall.

Edge shapes covered per the oracle-first contract (docs/KERNELS.md):
empty groups, one group owning the full batch, groups straddling tile
boundaries, K=1, and B not a multiple of the block size.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.gmm import ops as gmm_ops
from repro.kernels.imag import ops as imag_ops
from repro.kernels.imag import ref as imag_ref
from repro.kernels.ssd import ops as ssd_ops

KEY = jax.random.key(7)


def rand(shape, i, scale=1.0):
    return jax.random.normal(jax.random.fold_in(KEY, i), shape) * scale


# ------------------------------------------------------------ attention
@pytest.mark.parametrize("case", [
    # B, Sq, Sk, Hq, Hkv, D, causal, window
    (2, 128, 128, 4, 2, 64, True, 0),
    (1, 64, 192, 4, 1, 64, True, 64),     # prefix cache + sliding window
    (1, 64, 64, 2, 2, 32, False, 0),
])
def test_attention_ops_pallas_interpret_vs_ref(case):
    B, Sq, Sk, Hq, Hkv, D, causal, win = case
    q = rand((B, Sq, Hq, D), 1)
    k = rand((B, Sk, Hkv, D), 2)
    v = rand((B, Sk, Hkv, D), 3)
    out = fa_ops.attention(q, k, v, causal=causal, window=win,
                           impl="pallas", interpret=True)
    exp = fa_ref.naive_attention(q, k, v, causal=causal, window=win)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=2e-3, rtol=2e-3)


# ------------------------------------------------------------------ ssd
@pytest.mark.parametrize("case", [
    # B, L, H, P, N, G, chunk
    (2, 256, 4, 32, 16, 1, 64),
    (1, 100, 8, 16, 32, 2, 32),           # L not a multiple of chunk
])
def test_ssd_ops_pallas_interpret_vs_ref(case):
    B, L, H, P, N, G, chunk = case
    x = rand((B, L, H, P), 10, 0.5)
    dt = jax.nn.softplus(rand((B, L, H), 11))
    A = -jnp.exp(rand((H,), 12, 0.3))
    Bm = rand((B, L, G, N), 13, 0.3)
    C = rand((B, L, G, N), 14, 0.3)
    out = ssd_ops.ssd(x, dt, A, Bm, C, chunk=chunk, impl="pallas",
                      interpret=True)
    exp = ssd_ops.ssd(x, dt, A, Bm, C, chunk=chunk, impl="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=1e-3, rtol=1e-3)


# ------------------------------------------------------------------ gmm
RAGGED_CASES = [
    # n_groups, M, K_dim, N, group sizes (sum = M)
    (4, 64, 32, 48, (10, 0, 54, 0)),      # empty groups
    (3, 200, 130, 70, (200, 0, 0)),       # one group owns the full batch
    (5, 37, 16, 16, (5, 8, 0, 20, 4)),    # straddling odd-size tiles
    (1, 128, 128, 128, (128,)),           # K=1
    (3, 300, 96, 40, (1, 298, 1)),
]


@pytest.mark.parametrize("case", RAGGED_CASES)
def test_gmm_ops_ragged_pallas_interpret_vs_ref(case):
    G, M, Kd, N, sizes = case
    lhs = rand((M, Kd), 20, 0.3)
    rhs = rand((G, Kd, N), 21, 0.3)
    gs = jnp.array(sizes, jnp.int32)
    out = gmm_ops.grouped_matmul(lhs, rhs, gs, impl="pallas",
                                 interpret=True)
    exp = gmm_ops.grouped_matmul(lhs, rhs, gs, impl="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=1e-4, rtol=1e-4)


def test_gmm_ops_select_pallas_interpret_vs_ref():
    K, B, D, H = 3, 48, 12, 32
    members = {"w": [rand((K, D, H), 22, 0.3), rand((K, H, D), 23, 0.3)],
               "b": [rand((K, H), 24, 0.1), rand((K, D), 25, 0.1)]}
    x = rand((B, D), 26)
    idx = jax.random.randint(jax.random.fold_in(KEY, 27), (B,), 0, K)
    out = gmm_ops.ensemble_mlp_select(members, x, idx, impl="pallas",
                                      interpret=True)
    exp = gmm_ops.ensemble_mlp_select(members, x, idx, impl="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=1e-4, rtol=1e-4)


# The kernel has no autodiff rule; the dispatcher's custom_vjp must give
# the oracle's gradients (the model learner differentiates through it).
GRAD_CASES = (
    [("equal", (3, 50, 20, 30)),
     ("equal", (2, 200, 130, 70))]        # several M and K tiles
    + [("ragged", c) for c in RAGGED_CASES]
    + [("select", (3, 48, 12, 32)),
       ("masked_mse", (5, 40, 6, 3, 64))]
)


def _grads(f, args, impl):
    return jax.grad(lambda *a: jnp.sum(jnp.sin(f(*a, impl=impl))),
                    argnums=tuple(range(len(args))))(*args)


@pytest.mark.parametrize("kind,case", GRAD_CASES)
def test_gmm_ops_grad_pallas_interpret_vs_ref(kind, case, monkeypatch):
    if kind == "equal":
        G, M, Kd, N = case
        args = (rand((G, M, Kd), 80, 0.3), rand((G, Kd, N), 81, 0.3))
        f = lambda lhs, rhs, impl: gmm_ops.grouped_matmul(
            lhs, rhs, impl=impl, interpret=True)
    elif kind == "ragged":
        G, M, Kd, N, sizes = case
        gs = jnp.array(sizes, jnp.int32)
        args = (rand((M, Kd), 82, 0.3), rand((G, Kd, N), 83, 0.3))
        f = lambda lhs, rhs, impl: gmm_ops.grouped_matmul(
            lhs, rhs, gs, impl=impl, interpret=True)
    elif kind == "select":
        K, B, D, H = case
        args = ({"w": [rand((K, D, H), 84, 0.3), rand((K, H, D), 85, 0.3)],
                 "b": [rand((K, H), 86, 0.1), rand((K, D), 87, 0.1)]},
                rand((B, D), 88))
        idx = jax.random.randint(jax.random.fold_in(KEY, 89), (B,), 0, K)
        f = lambda m, x, impl: gmm_ops.ensemble_mlp_select(
            m, x, idx, impl=impl, interpret=True)
    else:
        from repro.mbrl import dynamics as DYN
        K, B, obs, act, hid = case
        cfg = DYN.EnsembleConfig(obs, act, hidden=hid, n_models=K)
        args = (DYN.init_ensemble(cfg, jax.random.fold_in(KEY, 90)),)
        o, a, o2 = rand((B, obs), 91), rand((B, act), 92), rand((B, obs), 93)
        w = jnp.arange(B) < B - 7            # masked ring tail
        dispatch = gmm_ops.ensemble_mlp

        def f(params, impl):
            monkeypatch.setattr(
                gmm_ops, "ensemble_mlp",
                lambda m, x: dispatch(m, x, impl=impl, interpret=True))
            return DYN.masked_mse_loss(params, o, a, o2, w)
    exp = _grads(f, args, "ref")
    got = _grads(f, args, "pallas")
    for e, g in zip(jax.tree.leaves(exp), jax.tree.leaves(got)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(e),
                                   atol=1e-4, rtol=1e-4)


# ----------------------------------------------------------------- imag
def _imag_inputs(K, B, obs, act, hid, phid, i0=30):
    din = obs + act
    members = {"w": [rand((K, din, hid), i0, 0.3),
                     rand((K, hid, hid), i0 + 1, 0.3),
                     rand((K, hid, obs), i0 + 2, 0.3)],
               "b": [rand((K, hid), i0 + 3, 0.1),
                     rand((K, hid), i0 + 4, 0.1),
                     rand((K, obs), i0 + 5, 0.1)]}
    norm = {"mu_in": rand((din,), i0 + 6, 0.1),
            "sig_in": jnp.abs(rand((din,), i0 + 7)) + 0.5,
            "mu_out": rand((obs,), i0 + 8, 0.05),
            "sig_out": jnp.abs(rand((obs,), i0 + 9)) + 0.5}
    pol = {"w": [rand((obs, phid), i0 + 10, 0.3),
                 rand((phid, act), i0 + 11, 0.3)],
           "b": [jnp.zeros((phid,)), jnp.zeros((act,))],
           "log_std": jnp.full((act,), -0.5)}
    s = rand((B, obs), i0 + 12)
    eps = rand((B, act), i0 + 13)
    return members, norm, pol, s, eps


IMAG_CASES = [
    # K, B, obs, act, hid, phid, block_b, midx mode
    (3, 48, 3, 1, 96, 48, 128, "rand"),    # bench shape, single tile
    (3, 48, 3, 1, 96, 48, 16, "one"),      # full group + empties, tiled
    (3, 48, 3, 1, 96, 48, 16, "rand"),     # groups straddle tiles
    (1, 20, 5, 2, 32, 16, 8, "rand"),      # K=1, B not tile multiple
    (5, 37, 4, 2, 24, 12, 8, "rand"),
]


@pytest.mark.parametrize("case,planned", [
    pytest.param(c, p, id=f"case{i}" + ("-plan" if p else ""))
    for p in (False, True) for i, c in enumerate(IMAG_CASES)])
def test_imag_ops_impls_vs_oracle(case, planned):
    K, B, obs, act, hid, phid, bb, mode = case
    members, norm, pol, s, eps = _imag_inputs(K, B, obs, act, hid, phid)
    if mode == "one":
        midx = jnp.full((B,), min(1, K - 1), jnp.int32)
    else:
        midx = jax.random.randint(jax.random.fold_in(KEY, 50), (B,), 0, K)
    plan = imag_ops.sort_plan(midx, K) if planned else None
    exp = imag_ref.fused_step(members, norm, pol, s, eps, midx)
    got_flat = imag_ops.fused_step(members, norm, pol, s, eps, midx,
                                   impl="fused")
    got_pal = imag_ops.fused_step(members, norm, pol, s, eps, midx,
                                  impl="pallas", interpret=True,
                                  block_b=bb, plan=plan)
    for got in (got_flat, got_pal):
        for e, g in zip(exp, got):
            np.testing.assert_allclose(np.asarray(g), np.asarray(e),
                                       atol=1e-4, rtol=1e-4)


def test_imag_sort_plan_inverse():
    midx = jax.random.randint(jax.random.fold_in(KEY, 51), (4, 37), 0, 5)
    order, offsets, inv = imag_ops.sort_plan(midx, 5)
    rows = np.broadcast_to(np.arange(37), (4, 37))
    np.testing.assert_array_equal(
        np.take_along_axis(np.asarray(inv), np.asarray(order), -1), rows)
    np.testing.assert_array_equal(
        np.take_along_axis(np.asarray(order), np.asarray(inv), -1), rows)
    np.testing.assert_array_equal(np.asarray(offsets[:, -1]), 37)


def test_imag_pallas_step_moves_rows_by_two_gathers():
    """With the plan given, one step's rows go in by one gather and come
    back by one gather; no scatter unsorts them (the kernel's own inner
    jaxpr is not counted)."""
    K, B, obs, act = 3, 24, 3, 1
    members, norm, pol, s, eps = _imag_inputs(K, B, obs, act, 16, 8)
    midx = jax.random.randint(jax.random.fold_in(KEY, 52), (B,), 0, K)
    plan = imag_ops.sort_plan(midx, K)
    jaxpr = jax.make_jaxpr(lambda *a: imag_ops.fused_step(
        *a, impl="pallas", interpret=True, block_b=8, plan=plan))(
        members, norm, pol, s, eps, midx)
    prims = [e.primitive.name for e in jaxpr.jaxpr.eqns]
    assert prims.count("gather") == 2, prims
    assert not [p for p in prims if p.startswith("scatter")], prims


def _scatter_unsort_step(members, norm, pol, s, eps, member_idx, *,
                         plan, **_):
    """The pallas step as spelled before the inverse permutation: two
    row gathers in, one scatter per output back to input order."""
    from repro.kernels.imag import pallas as imag_pallas
    order, offsets, _ = plan
    x = jnp.concatenate([s[order], eps[order]], axis=1)
    out = imag_pallas.fused_step_sorted(members, norm, pol, x, offsets,
                                        block_b=8, interpret=True)
    unsort = lambda v: jnp.zeros_like(v).at[order].set(v)
    o, a = s.shape[1], eps.shape[1]
    return (unsort(out[:, :o]), unsort(out[:, o:o + a]),
            unsort(out[:, o + a:]))


def test_imag_pallas_rollout_matches_scatter_unsort_and_ref(monkeypatch):
    """A whole horizon through ``_rollout_with_logp`` on the pallas path:
    bitwise the rollout of the scatter spelling, and the oracle's to
    float tolerance."""
    from repro.mbrl import algos
    K, B, obs, act, H = 3, 20, 5, 2, 6
    members, norm, pol, _, _ = _imag_inputs(K, B, obs, act, 24, 12, i0=80)
    params = {"members": members, "norm": norm}
    s0 = rand((B, obs), 95)
    # no sum over features: XLA may order a reduction differently around
    # a gather than around a scatter, and the rows are what is compared
    reward = lambda s, a, s2: s2[:, 0] * s[:, -1] - a[:, 0] ** 2

    def rollout(impl, step):
        monkeypatch.setattr(imag_ops, "default_impl", lambda: impl)
        monkeypatch.setattr(imag_ops, "fused_step", step)
        return algos._rollout_with_logp(params, pol, s0, jax.random.key(3),
                                        H, reward)

    fused_step = imag_ops.fused_step
    new = rollout("pallas", lambda *a, interpret=False, **kw: fused_step(
        *a, interpret=True, block_b=8, **kw))
    old = rollout("pallas", _scatter_unsort_step)
    ref = rollout("ref", fused_step)
    for n, o, r in zip(new, old, ref):
        np.testing.assert_array_equal(np.asarray(n), np.asarray(o))
        np.testing.assert_allclose(np.asarray(n), np.asarray(r),
                                   atol=1e-4, rtol=1e-4)


def test_imag_pallas_grad_matches_ref():
    """The fused step stays differentiable on every impl — the
    megakernel's custom_vjp must agree with grads of the oracle, the step
    sorting its own rows or given the plan."""
    K, B, obs, act, hid, phid = 3, 20, 3, 1, 16, 8
    members, norm, pol, s, eps = _imag_inputs(K, B, obs, act, hid, phid,
                                              i0=60)
    midx = jax.random.randint(jax.random.fold_in(KEY, 70), (B,), 0, K)

    def loss(impl, plan=None):
        def f(mem, po, ss):
            s2, a, pre = imag_ops.fused_step(mem, norm, po, ss, eps, midx,
                                             impl=impl, interpret=True,
                                             block_b=8, plan=plan)
            return jnp.sum(s2 ** 2) + jnp.sum(a * pre)
        return jax.grad(f, argnums=(0, 1, 2))(members, pol, s)

    g_ref = loss("ref")
    for plan in (None, imag_ops.sort_plan(midx, K)):
        g_pal = loss("pallas", plan)
        for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_pal)):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       atol=1e-4, rtol=1e-4)


def test_imag_pallas_vmapped_over_member_slices():
    """The pallas step vmapped over K one-member slices of the ensemble,
    each with its own rows, noise and plan, so the scalar-prefetch
    offsets are batched too. JAX lowers that to one kernel call per
    slice; each must be the unbatched call's, and the oracle's to float
    tolerance. (MB-MPO maps its members' rollouts with ``lax.map``
    instead, which keeps each call a lone operation in a device trace.)"""
    K, B, obs, act = 3, 20, 5, 2
    members, norm, pol, _, _ = _imag_inputs(K, B, obs, act, 24, 12, i0=100)
    sliced = jax.tree.map(lambda x: x[:, None], members)    # (K, 1, ...)
    s = rand((K, B, obs), 120)
    eps = rand((K, B, act), 121)
    midx = jnp.zeros((K, B), jnp.int32)

    def one(m, s, eps, idx):
        return imag_ops.fused_step(m, norm, pol, s, eps, idx, impl="pallas",
                                   interpret=True, block_b=8,
                                   plan=imag_ops.sort_plan(idx, 1))

    got = jax.vmap(one)(sliced, s, eps, midx)
    for k in range(K):
        m = jax.tree.map(lambda x: x[k], sliced)
        alone = one(m, s[k], eps[k], midx[k])
        exp = imag_ref.fused_step(m, norm, pol, s[k], eps[k], midx[k])
        for g, a, e in zip(got, alone, exp):
            np.testing.assert_array_equal(np.asarray(g[k]), np.asarray(a))
            np.testing.assert_allclose(np.asarray(g[k]), np.asarray(e),
                                       atol=1e-4, rtol=1e-4)


def test_mbmpo_improve_pallas_matches_fused(monkeypatch):
    """One MB-MPO step with imagination on the interpreted pallas path
    (one one-member call per member and horizon step) against the
    XLA-fused path: the same rollouts to float tolerance, so the same
    meta-gradient."""
    from repro.envs import make_env
    from repro.mbrl import dynamics as DYN
    from repro.mbrl.algos import AlgoConfig, make_algo
    from repro.mbrl.policy import PolicyConfig
    env = make_env("pendulum")
    model = DYN.init_ensemble(DYN.EnsembleConfig(
        env.obs_dim, env.act_dim, hidden=24, n_models=3), jax.random.key(8))
    acfg = AlgoConfig(algo="mb-mpo", imagine_batch=20, imagine_horizon=4,
                      n_models=3)
    fused_step = imag_ops.fused_step

    def improve(impl, step):
        monkeypatch.setattr(imag_ops, "default_impl", lambda: impl)
        monkeypatch.setattr(imag_ops, "fused_step", step)
        algo = make_algo(acfg, PolicyConfig(env.obs_dim, env.act_dim,
                                            hidden=16),
                         jax.vmap(env.reward), env.reset_batch)
        state = algo.init(jax.random.key(9))
        return algo.improve(state, model, jax.random.key(10))

    pal, pal_info = improve("pallas", lambda *a, interpret=False, **kw:
                            fused_step(*a, interpret=True, block_b=8, **kw))
    ref, ref_info = improve("fused", fused_step)
    np.testing.assert_allclose(float(pal_info["imagined_return"]),
                               float(ref_info["imagined_return"]),
                               rtol=1e-5)
    # Adam's first moment is the meta-gradient; the parameters took
    # Adam's normalised step, which rounding near zero can flip
    for a, b in zip(jax.tree.leaves(pal["opt"].mu),
                    jax.tree.leaves(ref["opt"].mu)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-4)
