"""Dynamics-model ensembles (the paper's p-hat_phi_1..K).

An ensemble of K MLPs trained on (s, a) -> delta-s with input/output
normalisation; sampling uses a uniform prior over ensemble members
(Section 3 of the paper).

Training evaluates every member on every row (``ensemble_mlp``: Pallas
grouped matmul on TPU; pure-jnp reference elsewhere). Imagination only
SAMPLES one member per row, so it must not PAY for all K: the hot path is
``predict_assigned`` — draw member indices up front (``sample_members``),
then per batch sort rows by member, run ONE ragged grouped MLP forward
over the (B, .) batch (B rows of FLOPs instead of K*B) and unsort
(``ensemble_mlp_select``). ``predict`` keeps the legacy
compute-all-then-select contract; under the same member assignment both
return the same next states."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.kernels.gmm import ops as gmm_ops
from repro.kernels.imag import ops as imag_ops
from repro.kernels.mesh import on_mesh, row_axes
from repro.mbrl import policy as PI
from repro.optim.optimizers import adam, apply_updates
from repro.utils.jit_stats import trace_counted


@dataclasses.dataclass(frozen=True)
class EnsembleConfig:
    obs_dim: int
    act_dim: int
    hidden: int = 256
    depth: int = 2
    n_models: int = 5
    lr: float = 1e-3
    train_batch: int = 256
    holdout_frac: float = 0.2


def init_member(cfg: EnsembleConfig, key):
    dims = [cfg.obs_dim + cfg.act_dim] + [cfg.hidden] * cfg.depth \
        + [cfg.obs_dim]
    ks = jax.random.split(key, len(dims) - 1)
    return {
        "w": [jax.random.normal(k, (a, b)) * (a ** -0.5)
              for k, a, b in zip(ks, dims[:-1], dims[1:])],
        "b": [jnp.zeros((b,)) for b in dims[1:]],
    }


def init_ensemble(cfg: EnsembleConfig, key):
    keys = jax.random.split(key, cfg.n_models)
    params = jax.vmap(lambda k: init_member(cfg, k))(keys)
    norm = {"mu_in": jnp.zeros(cfg.obs_dim + cfg.act_dim),
            "sig_in": jnp.ones(cfg.obs_dim + cfg.act_dim),
            "mu_out": jnp.zeros(cfg.obs_dim),
            "sig_out": jnp.ones(cfg.obs_dim)}
    return {"members": params, "norm": norm}


def update_normalizer(state, obs, act, next_obs):
    return {**state,
            "norm": masked_norm_stats(obs, act, next_obs, obs.shape[0])}


def member_forward(member, xn):
    h = xn
    n = len(member["w"])
    for i, (w, b) in enumerate(zip(member["w"], member["b"])):
        h = h @ w + b
        if i < n - 1:
            h = jnp.tanh(h)
    return h


def ensemble_forward(params, obs, act):
    """Per-member predictions. obs/act: (B, ·) -> (K, B, obs_dim)."""
    x = jnp.concatenate([obs, act], -1)
    n = params["norm"]
    xn = (x - n["mu_in"]) / n["sig_in"]
    dyn = gmm_ops.ensemble_mlp(params["members"], xn)
    return obs[None] + dyn * n["sig_out"] + n["mu_out"]


def n_members(params) -> int:
    return params["members"]["w"][0].shape[0]


def sample_members(params, key, shape):
    """Uniform prior over ensemble members (Sec. 3): I ~ U[K], iid per
    element of ``shape``. Drawn OUTSIDE the imagination scan so the whole
    horizon's assignments cost one RNG op."""
    return jax.random.randint(key, shape, 0, n_members(params))


def predict_assigned(params, obs, act, member_idx):
    """Next-state prediction with rows pre-assigned to members.

    member_idx: (B,) int in [0, K). Row b is evaluated by member
    ``member_idx[b]`` ONLY — via the sort / ragged-grouped-matmul /
    unsort path (``ensemble_mlp_select``), so a batch costs B rows of
    FLOPs, not K*B. Identical output to ``predict`` under the same
    assignment."""
    x = jnp.concatenate([obs, act], -1)
    n = params["norm"]
    xn = (x - n["mu_in"]) / n["sig_in"]
    dyn = gmm_ops.ensemble_mlp_select(params["members"], xn, member_idx)
    return obs + dyn * n["sig_out"] + n["mu_out"]


def predict(params, obs, act, key):
    """Uniform-prior ensemble sample: s' ~ p_phi_I, I ~ U[K] (Sec. 3).
    Legacy compute-all-then-select path — it PAYS for all K members on
    every call. Hot loops must not use it: imagination goes through the
    fused step (``step_fused`` / the fused ``imagine_rollout``, one
    ``kernels/imag`` dispatch per horizon step), and one-off assigned
    predictions through ``sample_members`` + ``predict_assigned``."""
    preds = ensemble_forward(params, obs, act)           # (K, B, D)
    K = preds.shape[0]
    idx = jax.random.randint(key, (obs.shape[0],), 0, K)
    return jnp.take_along_axis(
        preds, idx[None, :, None], axis=0)[0]


def masked_mse_loss(params, obs, act, next_obs, weights):
    """MSE over rows where ``weights`` is 1 — used against full-capacity
    ring storage, where rows past the valid count are garbage."""
    n = params["norm"]
    target = (next_obs - obs - n["mu_out"]) / n["sig_out"]
    x = jnp.concatenate([obs, act], -1)
    xn = (x - n["mu_in"]) / n["sig_in"]
    pred = gmm_ops.ensemble_mlp(params["members"], xn)   # (K, B, D)
    per_row = jnp.mean((pred - target[None]) ** 2, axis=(0, 2))   # (B,)
    w = weights.astype(per_row.dtype)
    return jnp.sum(per_row * w) / jnp.maximum(jnp.sum(w), 1.0)


def mse_loss(params, obs, act, next_obs):
    return masked_mse_loss(params, obs, act, next_obs,
                           jnp.ones(obs.shape[0], obs.dtype))


def _sgd_epoch_scan(opt, params, opt_state, obs, act, next_obs, batches,
                    n_active=None, shard_batch=None):
    """Scan minibatch SGD over precomputed (nb, bs) index batches —
    shared by the legacy and ring trainers.

    ``n_active`` (traced scalar, optional) limits the epoch to the first
    ``n_active`` batches WITHOUT changing the compiled shape: excess
    batches are skipped at runtime via lax.cond (one branch executes in
    an un-vmapped scan), so a ring trainer's static grid does
    epoch-proportional work on a partially filled buffer and full grid
    work only at steady state.

    ``shard_batch`` (optional, x -> x): sharding constraint applied to
    each gathered minibatch — the data-parallel hook for role sub-meshes
    (params replicated, per-device grads, XLA inserts the psum)."""

    def sgd(p, o, idx):
        mb = (obs[idx], act[idx], next_obs[idx])
        if shard_batch is not None:
            mb = tuple(shard_batch(x) for x in mb)
        loss, g = jax.value_and_grad(mse_loss)(p, *mb)
        upd, o = opt.update(g, o, p)
        return apply_updates(p, upd), o, loss

    def step(carry, xs):
        i, idx = xs
        p, o = carry
        if n_active is None:
            p2, o2, loss = sgd(p, o, idx)
            return (p2, o2), loss
        p2, o2, loss = jax.lax.cond(
            i < n_active, sgd,
            lambda p, o, idx: (p, o, jnp.zeros((), obs.dtype)), p, o, idx)
        return (p2, o2), loss

    nb = batches.shape[0]
    (params, opt_state), losses = jax.lax.scan(
        step, (params, opt_state), (jnp.arange(nb), batches))
    if n_active is None:
        return params, opt_state, losses.mean()
    return params, opt_state, losses.sum() / jnp.maximum(n_active, 1)


def make_model_trainer(cfg: EnsembleConfig):
    """Legacy dynamic-shape trainer (retraces when the data size changes;
    prefer make_ring_trainer on the hot path)."""
    opt = adam(cfg.lr)

    @jax.jit
    def train_epoch(params, opt_state, obs, act, next_obs, key):
        """One epoch of minibatch SGD over the (shuffled) buffer."""
        n = obs.shape[0]
        bs = min(cfg.train_batch, n)
        nb = max(n // bs, 1)
        perm = jax.random.permutation(key, n)[:nb * bs]
        return _sgd_epoch_scan(opt, params, opt_state, obs, act, next_obs,
                               perm.reshape(nb, bs))

    @jax.jit
    def val_loss(params, obs, act, next_obs):
        return mse_loss(params, obs, act, next_obs)

    return opt, train_epoch, val_loss


def masked_norm_stats(obs, act, next_obs, size):
    """Normalizer stats against ring storage: moments over the first
    ``size`` valid rows (``size`` is traced — shapes stay static).
    Returns only the ``norm`` dict so a jitted caller never copies the
    ensemble members."""
    w = (jnp.arange(obs.shape[0]) < size).astype(obs.dtype)
    tot = jnp.maximum(w.sum(), 1.0)

    def moments(v):
        mu = (v * w[:, None]).sum(0) / tot
        var = (((v - mu) ** 2) * w[:, None]).sum(0) / tot
        return mu, jnp.sqrt(var) + 1e-4

    x = jnp.concatenate([obs, act], -1)
    dy = next_obs - obs
    mu_in, sig_in = moments(x)
    mu_out, sig_out = moments(dy)
    return {"mu_in": mu_in, "sig_in": sig_in,
            "mu_out": mu_out, "sig_out": sig_out}


def make_ring_trainer(cfg: EnsembleConfig, capacity: int,
                      *, epoch_batches: int | None = None,
                      max_epoch_batches: int = 64,
                      batch_sharding=None):
    """Retrace-free trainer over fixed-capacity ring storage.

    All three returned functions close over STATIC shapes only
    (``capacity`` and the static minibatch grid), so each compiles exactly
    once regardless of how full the buffer is:

    * ``update_norm(data, size)`` — masked normalizer stats (returns the
      ``norm`` dict only, so no ensemble-member copy per refresh).
    * ``train_epoch(params, opt_state, data, size, key)`` — a fixed grid
      of ``nb`` minibatches of ``cfg.train_batch`` indices sampled
      uniformly (with replacement) from the valid region ``[0, size)``;
      only the first ``clip(size // bs, 1, nb)`` batches apply their
      updates, so one epoch is one pass over the CURRENT data (like the
      legacy trainer) while the compiled shape never changes.
      ``params``/``opt_state`` are donated so the optimizer updates in
      place where the backend supports buffer aliasing.
    * ``val_loss(params, data, size)`` — masked MSE over a val ring.

    ``train_epoch`` and ``val_loss`` carry a ``.trace_count`` attribute
    (see repro.utils.jit_stats) so benchmarks/tests can assert the
    no-retrace invariant.

    ``batch_sharding`` (role meshes): a ``NamedSharding`` over the owning
    sub-mesh's batch axis. Ring storage arrives pre-sharded from
    :class:`repro.core.servers.ReplayBuffer`; each gathered minibatch is
    constrained to the same sharding so the SGD step runs data-parallel
    (params replicated, per-device grads psum'd by XLA). Same math, same
    compile-once guarantee.
    """
    opt = adam(cfg.lr)
    bs = min(cfg.train_batch, max(int(capacity), 1))
    nb = epoch_batches if epoch_batches is not None else \
        min(max(int(capacity) // bs, 1), max_epoch_batches)
    shard_batch = None
    if batch_sharding is not None:
        shard_batch = lambda x: jax.lax.with_sharding_constraint(
            x, batch_sharding)

    # the ensemble kernels run per shard of the owning sub-mesh
    mesh = None if batch_sharding is None else batch_sharding.mesh

    def _train_epoch(params, opt_state, data, size, key):
        idx = jax.random.randint(key, (nb, bs), 0,
                                 jnp.maximum(size, 1))
        # one pass over the VALID region per epoch (like the legacy
        # trainer), not over the whole capacity grid
        n_active = jnp.clip(size // bs, 1, nb)
        with on_mesh(mesh):
            return _sgd_epoch_scan(opt, params, opt_state, data["obs"],
                                   data["act"], data["next_obs"], idx,
                                   n_active=n_active,
                                   shard_batch=shard_batch)

    def _val_loss(params, data, size):
        w = jnp.arange(data["obs"].shape[0]) < size
        with on_mesh(mesh):
            return masked_mse_loss(params, data["obs"], data["act"],
                                   data["next_obs"], w)

    def _update_norm(data, size):
        return masked_norm_stats(data["obs"], data["act"],
                                 data["next_obs"], size)

    train_epoch = trace_counted(_train_epoch, donate_argnums=(0, 1))
    val_loss = trace_counted(_val_loss)
    update_norm = trace_counted(_update_norm)
    return opt, train_epoch, val_loss, update_norm


def step_fused(params, policy_params, s, eps, member_idx, *, impl=None,
               interpret=False, plan=None):
    """One FUSED imagination step: policy head + reparameterised action
    + assigned-member dynamics forward as a single ``kernels/imag``
    dispatch (Pallas megakernel on TPU, one flat XLA body elsewhere).

    s: (B, obs); eps: (B, act) standard normal (pre-drawn — hoist the
    whole horizon's draws out of the scan); member_idx: (B,) int.
    ``plan``: precomputed ``imag_ops.sort_plan`` slice for this step's
    assignment (pallas impl; keeps the sort/unsort out of the scan body).
    Returns ``(s2, a, pre)``."""
    return imag_ops.fused_step(params["members"], params["norm"],
                               policy_params, s, eps, member_idx,
                               impl=impl, interpret=interpret, plan=plan)


def horizon_plan(params, member_idx):
    """Sort/unsort plans for a whole horizon of member assignments
    ((H, B) int), for threading through a rollout scan: per step the
    member order, the group offsets and the inverse order, so each step
    moves its rows by one gather in and one gather out. None when the
    backend's fused impl doesn't sort (the flat XLA path is
    row-order-blind, so no plan is ever computed on CPU/GPU), or sorts
    per shard of a multi-device ambient mesh (``kernels/mesh.py``)."""
    if imag_ops.default_impl() != "pallas" or row_axes() is not None:
        return None
    return imag_ops.sort_plan(member_idx, n_members(params))


def hoisted_noise(key, horizon, batch, act_dim):
    """The whole horizon's policy noise in one op, bit-identical to the
    per-step ``normal(keys[h], (B, act))`` draws of the legacy scan."""
    return jax.vmap(lambda k: jax.random.normal(k, (batch, act_dim)))(
        jax.random.split(key, horizon))


def imagine_rollout(params, policy_fn, policy_params, s0, key, horizon,
                    reward_fn, *, fused=None):
    """Dyna imagination: roll the ensemble from s0 under the policy.

    s0: (B, D). Returns dict with (H, B, ·) arrays. Sample-then-compute:
    the whole horizon's member assignments AND policy noise are drawn up
    front, and each step is ONE fused ``step_fused`` dispatch (policy
    head + assigned-member dynamics, no K* ensemble overcompute and no
    per-step sort inside the scan).

    ``fused=None`` auto-detects: the fused path replicates exactly the
    tanh-Gaussian ``PI.sample_action``, so any other ``policy_fn`` (or
    ``fused=False``) takes the legacy per-step path
    (``policy_fn`` + ``predict_assigned``) instead."""
    if fused is None:
        fused = policy_fn is PI.sample_action
    ka, kp = jax.random.split(key)
    members = sample_members(params, kp, (horizon, s0.shape[0]))
    keys = jax.random.split(ka, horizon)

    if not fused:
        def step(carry, xs):
            k, midx = xs
            s = carry
            a = policy_fn(policy_params, s, k)
            s2 = predict_assigned(params, s, a, midx)
            r = reward_fn(s, a, s2)
            return s2, (s, a, r)

        _, (obs, act, rew) = jax.lax.scan(step, s0, (keys, members))
        return {"obs": obs, "act": act, "rew": rew}

    act_dim = policy_params["w"][-1].shape[1]
    eps = hoisted_noise(ka, horizon, s0.shape[0], act_dim)
    plan = horizon_plan(params, members)

    def step(carry, xs):
        e, midx, pl_ = xs
        s = carry
        s2, a, _pre = step_fused(params, policy_params, s, e, midx,
                                 plan=pl_)
        r = reward_fn(s, a, s2)
        return s2, (s, a, r)

    _, (obs, act, rew) = jax.lax.scan(step, s0, (eps, members, plan))
    return {"obs": obs, "act": act, "rew": rew}
