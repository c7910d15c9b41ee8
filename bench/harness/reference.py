"""Plain reference of the trainer's set-up steps.

Straightforward ``jax.numpy`` in float32, written from the algorithms'
descriptions and sharing no code with the program: the arm's dynamics
and reward, the tanh-Gaussian policy, the farm's rollouts, the FIFO ring
with its held-out trajectories, the dynamics ensemble (every member on
every row) with its normaliser and Adam, imagination from the ensemble
and the batch of advantages a policy step learns from. (The set-up's
model steps each follow a landing, which resets the program's early
stop, so none can stop.) The policy learner's state and step are the
algorithm's own file, ``bench/algos/<algo>.py``, found by the
configuration's ``algo`` (``cells.algorithm``). It builds its own
weights and data from the seed, drawing them as the configuration's
random streams prescribe, and never sees what the program made.

Every matrix product goes through ``Matmul``: exact float32 (``HIGHEST``)
for the reference, or bf16x3 (three bf16 products, what ``high``
computes) for the lower-precision control. The control is emulated
operand by operand, so it means the same on any backend.

``fault`` plants one of the faults the check must catch, in the
reference put in the program's place (``check.py``): ``"half_batch"``
takes every mean of the model learner's and the policy learner's
losses over half of the batch.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import cells

HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Matmul:
    bf16x3: bool = False

    def split(self, x):
        hi = x.astype(jnp.bfloat16)
        lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
        return hi, lo

    def dot(self, a, b, spec):
        if not self.bf16x3:
            return jnp.einsum(spec, a, b, precision=HIGHEST,
                              preferred_element_type=jnp.float32)
        (ah, al), (bh, bl) = self.split(a), self.split(b)
        f = lambda x, y: jnp.einsum(spec, x, y, precision=HIGHEST,
                                    preferred_element_type=jnp.float32)
        return f(ah, bh) + (f(ah, bl) + f(al, bh))

    def __call__(self, a, b):
        """a (..., n) @ b (n, m)."""
        return self.dot(a, b, "...n,nm->...m")


# ------------------------------------------------------------------ arm
TARGET = (0.5, 0.2, 0.3)        # the reach task's end-effector target
LINK = 0.18


def arm_fk(q, mm):
    """End-effector origin and two frame points of the 7-joint chain,
    joints alternating about z and y."""
    p = jnp.zeros(3)
    R = jnp.eye(3)
    for i in range(7):
        c, s = jnp.cos(q[i]), jnp.sin(q[i])
        if i % 2 == 0:
            Ri = jnp.array([[c, -s, 0.0], [s, c, 0.0], [0.0, 0.0, 1.0]])
        else:
            Ri = jnp.array([[c, 0.0, s], [0.0, 1.0, 0.0], [-s, 0.0, c]])
        R = mm.dot(R, Ri, "ij,jk->ik")
        p = p + mm.dot(R, jnp.array([LINK, 0.0, 0.0]), "ij,j->i")
    return jnp.concatenate([p, p + 0.05 * R[:, 0], p + 0.05 * R[:, 1]])


def arm_obs(q, qd, mm):
    return jnp.concatenate([q, qd, arm_fk(q, mm)])


def arm_reset(key, mm):
    return arm_obs(0.1 * jax.random.normal(key, (7,)), jnp.zeros(7), mm)


def arm_reward(s, a, s2):
    u = jnp.clip(a, -1.0, 1.0)
    d2 = jnp.sum((s2[..., 14:17] - jnp.asarray(TARGET)) ** 2, -1)
    return (-d2 - jnp.log(d2 + 1e-5) - 0.05 * jnp.sum(s2[..., 7:14] ** 2, -1)
            - 0.01 * jnp.sum(u ** 2, -1))


def arm_step(s, a, dt, mm):
    q, qd = s[:7], s[7:14]
    u = jnp.clip(a, -1.0, 1.0)
    qd = jnp.clip(qd + (6.0 * u - qd - 0.3 * jnp.sin(q)) * dt, -4.0, 4.0)
    q = jnp.clip(q + qd * dt, -2.8, 2.8)
    return arm_obs(q, qd, mm)


# --------------------------------------------------------------- policy
def mlp_init(key, dims):
    ks = jax.random.split(key, len(dims) - 1)
    return ([jax.random.normal(k, (a, b)) * (a ** -0.5)
             for k, a, b in zip(ks, dims[:-1], dims[1:])],
            [jnp.zeros((b,)) for b in dims[1:]])


def policy_init(key, c):
    w, b = mlp_init(key, [c["obs_dim"]] + [c["policy_hidden"]]
                    * c["policy_depth"] + [c["act_dim"]])
    return {"w": w, "b": b, "log_std": jnp.full(
        (c["act_dim"],), c["policy_init_log_std"], jnp.float32)}


def policy_mean(p, obs, mm):
    h = obs
    for i, (w, b) in enumerate(zip(p["w"], p["b"])):
        h = mm(h, w) + b
        if i < len(p["w"]) - 1:
            h = jnp.tanh(h)
    return h


def log_prob(p, obs, pre, mm):
    z = (pre - policy_mean(p, obs, mm)) / jnp.exp(p["log_std"])
    return jnp.sum(-0.5 * z ** 2 - p["log_std"] - 0.5 * math.log(2 * math.pi),
                   -1)


# ----------------------------------------------------------------- farm
def rollout(key, pol, c, mm):
    """One trajectory: reset from the first half of the key, then one
    noise draw per control step from the second."""
    k0, kr = jax.random.split(key)
    dt = 1.0 / c["control_hz"]

    def step(s, k):
        eps = jax.random.normal(k, (c["act_dim"],))
        a = jnp.tanh(policy_mean(pol, s, mm)
                     + jnp.exp(pol["log_std"]) * eps)
        s2 = arm_step(s, a, dt, mm)
        return s2, (s, a, s2, arm_reward(s, a, s2))

    _, (obs, act, nobs, rew) = jax.lax.scan(
        step, arm_reset(k0, mm), jax.random.split(kr, c["horizon"]))
    return {"obs": obs, "act": act, "next_obs": nobs, "rew": rew}


def farm_step(key, pol, n, c, mm):
    """n robots: lane 0 runs on ``key`` itself, lane i on fold_in(key, i)."""
    lanes = jnp.stack([key] + [jax.random.fold_in(key, i)
                               for i in range(1, n)])
    return jax.vmap(lambda k: rollout(k, pol, c, mm))(lanes)


# ----------------------------------------------------------------- ring
class Ring:
    """FIFO transition ring and its held-out ring, on the host: every
    ``every``-th trajectory (counting from 1) goes to the held-out ring."""

    def __init__(self, rows, val_rows, every, fields):
        self.train = {k: np.zeros((rows,) + s, np.float32)
                      for k, s in fields.items()}
        self.val = {k: np.zeros((val_rows,) + s, np.float32)
                    for k, s in fields.items()}
        self.every, self.n = every, 0
        self.cur = {"train": 0, "val": 0}
        self.written = {"train": 0, "val": 0}

    def add(self, traj):
        part, rows = self.skip(len(traj["obs"]))
        ring = self.val if part == "val" else self.train
        for k in ring:
            ring[k][rows] = traj[k]

    def skip(self, h):
        """Take the place of one trajectory of ``h`` rows without writing
        it; returns its part and rows."""
        self.n += 1
        part = "val" if self.n % self.every == 0 else "train"
        cap = len((self.val if part == "val" else self.train)["obs"])
        rows = (self.cur[part] + np.arange(h)) % cap
        self.cur[part] = (self.cur[part] + h) % cap
        self.written[part] += h
        return part, rows

    def view(self, part):
        ring = self.val if part == "val" else self.train
        return ({k: jnp.asarray(v) for k, v in ring.items()},
                min(self.written[part], len(ring["obs"])))


# ------------------------------------------------------------- ensemble
def ensemble_init(key, c):
    din = c["obs_dim"] + c["act_dim"]
    dims = [din] + [c["model_hidden"]] * c["model_depth"] + [c["obs_dim"]]
    members = [mlp_init(k, dims)
               for k in jax.random.split(key, c["n_models"])]
    return {"members": {"w": [jnp.stack([m[0][i] for m in members])
                              for i in range(len(dims) - 1)],
                        "b": [jnp.stack([m[1][i] for m in members])
                              for i in range(len(dims) - 1)]},
            "norm": {"mu_in": jnp.zeros(din), "sig_in": jnp.ones(din),
                     "mu_out": jnp.zeros(c["obs_dim"]),
                     "sig_out": jnp.ones(c["obs_dim"])}}


def members_forward(members, x, mm):
    """Every member on every row: (B, din) -> (K, B, dout)."""
    h = jnp.broadcast_to(x, (members["w"][0].shape[0],) + x.shape)
    n = len(members["w"])
    for i, (w, b) in enumerate(zip(members["w"], members["b"])):
        h = mm.dot(h, w, "kbi,kio->kbo") + b[:, None, :]
        if i < n - 1:
            h = jnp.tanh(h)
    return h


def model_loss(params, obs, act, nobs, weights, mm):
    """Mean over weighted rows of the squared error of every member's
    normalised delta prediction."""
    n = params["norm"]
    xn = (jnp.concatenate([obs, act], -1) - n["mu_in"]) / n["sig_in"]
    target = (nobs - obs - n["mu_out"]) / n["sig_out"]
    err = jnp.mean((members_forward(params["members"], xn, mm)
                    - target[None]) ** 2, axis=(0, 2))
    return jnp.sum(err * weights) / jnp.maximum(jnp.sum(weights), 1.0)


def norm_stats(data, size):
    w = (jnp.arange(data["obs"].shape[0]) < size).astype(jnp.float32)
    tot = jnp.maximum(w.sum(), 1.0)

    def moments(v):
        mu = (v * w[:, None]).sum(0) / tot
        return mu, jnp.sqrt((((v - mu) ** 2) * w[:, None]).sum(0) / tot) + 1e-4

    mu_in, sig_in = moments(jnp.concatenate([data["obs"], data["act"]], -1))
    mu_out, sig_out = moments(data["next_obs"] - data["obs"])
    return {"mu_in": mu_in, "sig_in": sig_in,
            "mu_out": mu_out, "sig_out": sig_out}


def adam_init(params):
    z = jax.tree.map(jnp.zeros_like, params)
    return {"t": 0, "m": z, "v": jax.tree.map(jnp.zeros_like, params)}


def adam_update(params, grads, st, lr, b1=0.9, b2=0.999, eps=1e-8):
    t = jnp.asarray(st["t"], jnp.float32) + 1     # float32, as configured
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, st["m"], grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, st["v"], grads)
    bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
    new = jax.tree.map(
        lambda p, m, v: p - lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps),
        params, m, v)
    return new, {"t": t, "m": m, "v": v}


def half(weights):
    """The fault: the second half of the rows left out of the mean."""
    n = weights.shape[0]
    return weights * (jnp.arange(n) < n // 2)


@functools.partial(jax.jit, static_argnums=(9, 10))
def _sgd(params, m, v, t, obs, act, nobs, rows, weights, lr, mm):
    loss, g = jax.value_and_grad(model_loss)(
        params, obs[rows], act[rows], nobs[rows], weights, mm)
    new, st = adam_update(params, g, {"t": t, "m": m, "v": v}, lr)
    return new, st["m"], st["v"]


_model_loss = jax.jit(model_loss, static_argnums=5)


def model_epoch(params, opt, data, size, key, c, mm, fault):
    """One epoch: a fixed grid of minibatches drawn with replacement from
    the valid rows, of which the first size // batch (at least one, at
    most the grid) are applied, one Adam step each."""
    bs = min(c["model_train_batch"], c["ring_trajs"] * c["horizon"])
    grid = min(max(c["ring_trajs"] * c["horizon"] // bs, 1), 64)
    idx = jax.random.randint(key, (grid, bs), 0, max(size, 1))
    active = int(np.clip(size // bs, 1, grid))
    ones = jnp.ones(bs)
    weights = half(ones) if fault == "half_batch" else ones
    m, v, t = opt["m"], opt["v"], opt["t"]
    for i in range(active):
        params, m, v = _sgd(params, m, v, jnp.float32(t + i), data["obs"],
                            data["act"], data["next_obs"], idx[i], weights,
                            c["model_lr"], mm)
    return params, {"t": t + active, "m": m, "v": v}


def model_step(params, opt, ring, key, c, mm, fault):
    """Refresh the normaliser from the train ring, one epoch, then the
    loss on the held-out ring."""
    data, size = ring.view("train")
    params = {**params, "norm": norm_stats(data, size)}
    params, opt = model_epoch(params, opt, data, size, key, c, mm, fault)
    vdata, vsize = ring.view("val")
    w = (jnp.arange(vdata["obs"].shape[0]) < vsize).astype(jnp.float32)
    vloss = _model_loss(params, vdata["obs"], vdata["act"],
                        vdata["next_obs"], w, mm)
    return params, opt, float(vloss)


# ----------------------------------------------------------- imagination
def imagine(model, pol, key, c, mm):
    """Imagined rollouts: starts from the arm's reset distribution, one
    uniformly drawn member per row and step, actions from the policy."""
    B, H, K = c["imagine_batch"], c["imagine_horizon"], c["n_models"]
    k0, k1 = jax.random.split(key)
    s0 = jax.vmap(lambda k: arm_reset(k, mm))(jax.random.split(k0, B))
    ka, kp = jax.random.split(k1)
    members = jax.random.randint(kp, (H, B), 0, K)
    eps = jax.vmap(lambda k: jax.random.normal(k, (B, c["act_dim"])))(
        jax.random.split(ka, H))
    n = model["norm"]

    def step(s, xs):
        e, idx = xs
        pre = policy_mean(pol, s, mm) + jnp.exp(pol["log_std"]) * e
        a = jnp.tanh(pre)
        xn = (jnp.concatenate([s, a], -1) - n["mu_in"]) / n["sig_in"]
        dyn = members_forward(model["members"], xn, mm)
        dyn = jnp.take_along_axis(dyn, idx[None, :, None], axis=0)[0]
        s2 = s + dyn * n["sig_out"] + n["mu_out"]
        return s2, (s, pre, jax.vmap(arm_reward)(s, a, s2))

    _, (obs, pre, rew) = jax.lax.scan(step, s0, (eps, members))
    return obs, pre, rew


def advantages(rew, gamma):
    """Discounted reward-to-go, centred per step over the batch, then
    standardised."""
    def back(g, r):
        g = r + gamma * g
        return g, g
    _, rtg = jax.lax.scan(back, jnp.zeros_like(rew[0]), rew[::-1])
    rtg = rtg[::-1]
    adv = rtg - rtg.mean(axis=1, keepdims=True)
    return (adv - adv.mean()) / (adv.std() + 1e-8)


def policy_batch(state, model, key, c, mm, fault):
    """What a policy step of the ME algorithms learns from: imagination
    from the state's policy, flattened to rows of observations, pre-tanh
    actions and standardised advantages, the rows' weights (half of them
    under the half-batch fault), and the imagined return."""
    obs, pre, rew = _imagine(model, state["policy"], key, _frozen(c), mm)
    adv = advantages(rew, c["gamma"]).reshape(-1)
    obs = obs.reshape(-1, obs.shape[-1])
    pre = pre.reshape(-1, pre.shape[-1])
    ones = jnp.ones(obs.shape[0])
    weights = half(ones) if fault == "half_batch" else ones
    return obs, pre, adv, weights, float(rew.sum(0).mean())


class _frozen(dict):
    """A configuration dict usable as a static jit argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, repr(v)) for k, v in self.items())))


_imagine = jax.jit(imagine, static_argnums=(3, 4))
_farm_step = jax.jit(farm_step, static_argnums=(2, 3, 4))


# ---------------------------------------------------------------- set-up
def run_setup(c, traffic, seed, rounds, steps=3, mm=Matmul(), fault=None):
    """The set-up the harness drives, from the seed: ``rounds`` of (one
    farm step per collector, one model step), then ``steps`` policy
    steps. Returns what ``runner.setup`` records of the program."""
    key = jax.random.key(seed)
    k_farm, k_model, k_pol, _ = jax.random.split(key, 4)
    k_model, k_init = jax.random.split(k_model)
    k_pol, k_pinit = jax.random.split(k_pol)
    model = ensemble_init(k_init, c)
    pol = policy_init(k_pinit, c)
    algo = cells.algorithm(c["algo"])
    state = algo.init(pol, c)
    n = traffic["robots_per_collector"]
    every = max(int(round(1 / c["holdout_frac"])), 2)
    rows = c["ring_trajs"] * c["horizon"]
    fields = {"obs": (c["obs_dim"],), "act": (c["act_dim"],),
              "next_obs": (c["obs_dim"],), "rew": ()}
    ring = Ring(rows, max(rows // 4, 1), every, fields)
    opt = adam_init(model)
    out = {"val_loss": [], "imagined_return": [], "model0": model,
           "policy0": state}
    keys_farm = [jax.random.fold_in(k_farm, i) if i else k_farm
                 for i in range(traffic["collectors"])]
    trajs = []
    for r in range(rounds):
        for i in range(traffic["collectors"]):
            keys_farm[i], k = jax.random.split(keys_farm[i])
            batch = {k_: np.asarray(v) for k_, v in
                     _farm_step(k, pol, n, _frozen(c), mm).items()}
            trajs.append(batch)
            for lane in range(n):
                ring.add({k_: v[lane] for k_, v in batch.items()})
        k_model, k = jax.random.split(k_model)
        model, opt, vloss = model_step(model, opt, ring, k, c, mm, fault)
        out["val_loss"].append(vloss)
        if r == 0:
            out["model_opt1"] = opt["m"]
        if r == steps - 1:
            out["model3"] = model
    out["trajs"] = {k: np.concatenate([b[k] for b in trajs])
                    for k in trajs[0]}
    out["ring"], out["ring_val"] = ring.train, ring.val
    for s in range(steps):
        k_pol, k = jax.random.split(k_pol)
        state, ret = algo.step(state, model, k, c, mm, fault)
        out["imagined_return"].append(ret)
        if s == 0:
            out["policy1"] = state
        if s == steps - 1:
            out["policy3"] = state
    return jax.tree.map(np.asarray, out)


def ring_layout(trajs, c, every, start=0):
    """Where the FIFO rings put a sequence of trajectories (the ingest
    check: the program's own trajectories, laid out by the reference),
    after ``start`` trajectories of which only the count matters."""
    rows = c["ring_trajs"] * c["horizon"]
    fields = {k: v.shape[2:] for k, v in trajs.items()}
    ring = Ring(rows, max(rows // 4, 1), every, fields)
    for _ in range(start):
        ring.skip(trajs["obs"].shape[1])
    for i in range(len(trajs["obs"])):
        ring.add({k: v[i] for k, v in trajs.items()})
    return ring.train, ring.val
