"""Decoder-LM assembly for the dense / moe / vlm / ssm / hybrid families.

All step functions here are LOCAL (they run inside ``jax.shard_map``);
global entry points with jit + shardings are built in ``repro.models.api``.

Parameter pytree::

  params = {
    "embed":  {table, head, ln_f},
    "layers": block params stacked over num_layers (lax.scan consumes them),
    "shared": hybrid-only shared attention+mlp block (one set of weights,
              applied every ``attn_every`` layers — Zamba2-style),
  }

Hybrid layer order: for layer index i, the shared transformer block runs
BEFORE mamba layer i whenever i % attn_every == 0. Internally the stack is
processed as ``n_full`` groups of ``attn_every`` mamba layers plus a tail
group, so each shared-block invocation's KV cache is collected naturally.

KV-cache layouts are chosen statically by ``layers.decode_mode`` — see the
kind "W"/"A"/"B" docstring there.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S
from repro.models.config import ModelConfig, ShardCtx
from repro.optim.optimizers import Optimizer, apply_updates

AUX_COEF = 0.01


def _remat(fn, ctx):
    """Layer remat. With ctx.save_collectives, forward collective outputs
    are stored instead of re-communicated in the backward recompute."""
    if getattr(ctx, "save_collectives", False):
        policy = jax.checkpoint_policies.save_only_these_names("tp_reduce")
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)

# --------------------------------------------------------------------------
# per-layer block init/spec


def _block_kind(cfg: ModelConfig) -> str:
    if cfg.family in ("dense", "vlm"):
        return "dense"
    if cfg.family == "moe":
        return "moe"
    if cfg.family in ("ssm", "hybrid"):
        return "ssm"
    raise ValueError(cfg.family)


def init_block(cfg: ModelConfig, ctx: ShardCtx, key):
    kind = _block_kind(cfg)
    if kind == "dense":
        k1, k2 = jax.random.split(key)
        return {"attn": L.init_attn(cfg, ctx, k1),
                "mlp": L.init_mlp(cfg, ctx, k2)}
    if kind == "moe":
        k1, k2 = jax.random.split(key)
        return {"attn": L.init_attn(cfg, ctx, k1),
                "moe": M.init_moe(cfg, ctx, k2)}
    return {"mamba": S.init_mamba(cfg, ctx, key)}


def spec_block(cfg: ModelConfig, ctx: ShardCtx):
    kind = _block_kind(cfg)
    if kind == "dense":
        return {"attn": L.spec_attn(cfg, ctx), "mlp": L.spec_mlp(cfg, ctx)}
    if kind == "moe":
        return {"attn": L.spec_attn(cfg, ctx), "moe": M.spec_moe(cfg, ctx)}
    return {"mamba": S.spec_mamba(cfg, ctx)}


def _shared_cfg(cfg: ModelConfig) -> ModelConfig:
    return dataclasses.replace(cfg, family="dense")


def init_params(cfg: ModelConfig, ctx: ShardCtx, key):
    k_emb, k_layers, k_shared = jax.random.split(key, 3)
    keys = jax.random.split(k_layers, cfg.num_layers)
    params = {
        "embed": L.init_embed(cfg, ctx, k_emb),
        "layers": jax.vmap(lambda k: init_block(cfg, ctx, k))(keys),
    }
    if cfg.family == "hybrid":
        scfg = _shared_cfg(cfg)
        k1, k2 = jax.random.split(k_shared)
        params["shared"] = {"attn": L.init_attn(scfg, ctx, k1),
                            "mlp": L.init_mlp(scfg, ctx, k2)}
    return params


def _stack_spec(spec):
    return jax.tree.map(lambda s: P(*((None,) + tuple(s))), spec,
                        is_leaf=lambda x: isinstance(x, P))


def param_specs(cfg: ModelConfig, ctx: ShardCtx):
    specs = {
        "embed": L.spec_embed(cfg, ctx),
        "layers": _stack_spec(spec_block(cfg, ctx)),
    }
    if cfg.family == "hybrid":
        scfg = _shared_cfg(cfg)
        specs["shared"] = {"attn": L.spec_attn(scfg, ctx),
                           "mlp": L.spec_mlp(scfg, ctx)}
    return specs


def _hybrid_groups(cfg: ModelConfig):
    k = cfg.attn_every
    n_full = cfg.num_layers // k
    tail = cfg.num_layers - n_full * k
    return k, n_full, tail


def n_shared_invocations(cfg: ModelConfig) -> int:
    if cfg.family != "hybrid" or not cfg.attn_every:
        return 0
    _, n_full, tail = _hybrid_groups(cfg)
    return n_full + (1 if tail else 0)


# --------------------------------------------------------------------------
# full-sequence forward (train / prefill)


def _mamba_scan(cfg, ctx, lp_stacked, h, *, remat, collect):
    def body(h, lp):
        if collect:
            h, (st, tx, tbc) = S.mamba_forward(cfg, ctx, lp["mamba"], h,
                                               return_state=True)
            return h, (st, tx, tbc)
        return S.mamba_forward(cfg, ctx, lp["mamba"], h), ()

    if remat:
        body = _remat(body, ctx)
    return jax.lax.scan(body, h, lp_stacked)


def stack_forward(cfg: ModelConfig, ctx: ShardCtx, params, x, positions, *,
                  remat: bool = False, collect_cache: bool = False):
    """Run the whole layer stack. Returns (h, aux_loss_sum, cache_ys).

    cache_ys (when collect_cache):
      dense/moe: (k, v) stacked over L
      ssm:       (ssm_state, tail_x, tail_bc) stacked over L
      hybrid:    dict(ssm=…, conv_x=…, conv_bc=…, k=…, v=…) — kv stacked
                 over shared-block invocations.
    """
    kind = _block_kind(cfg)

    if cfg.family == "hybrid":
        return _hybrid_forward(cfg, ctx, params, x, positions, remat=remat,
                               collect_cache=collect_cache)

    if kind == "ssm":
        h, ys = _mamba_scan(cfg, ctx, params["layers"], x, remat=remat,
                            collect=collect_cache)
        return h, jnp.zeros((), jnp.float32), ys

    def body(h, lp):
        aux = jnp.zeros((), jnp.float32)
        if collect_cache:
            h, (k, v) = L.attn_forward(cfg, ctx, lp["attn"], h, positions,
                                       return_kv=True)
            ys = (k, v)
        else:
            h = L.attn_forward(cfg, ctx, lp["attn"], h, positions)
            ys = ()
        if kind == "moe":
            h, aux = M.moe_forward(cfg, ctx, lp["moe"], h)
        else:
            h = L.mlp_forward(cfg, ctx, lp["mlp"], h)
        return h, (aux, ys)

    rg = getattr(ctx, "remat_group", 0)
    if remat and rg > 1 and not collect_cache:
        # two-level remat: save only every rg-th layer input; the recompute
        # count per layer is unchanged, but it lets the microbatch count
        # shrink (fewer FSDP weight gathers) at bounded memory (§Perf h2).
        n_full = cfg.num_layers // rg
        tail = cfg.num_layers - n_full * rg
        lp = params["layers"]
        grouped = jax.tree.map(
            lambda a: a[:n_full * rg].reshape((n_full, rg) + a.shape[1:]),
            lp)

        def group_body(h, glp):
            h, (auxs, ys) = jax.lax.scan(body, h, glp)
            return h, auxs.sum()

        group_body = _remat(group_body, ctx)
        h, auxs = jax.lax.scan(group_body, x, grouped)
        aux_total = auxs.sum()
        if tail:
            lp_tail = jax.tree.map(lambda a: a[n_full * rg:], lp)
            h, tail_aux = group_body(h, lp_tail)
            aux_total = aux_total + tail_aux
        return h, aux_total, ()
    if remat:
        body = _remat(body, ctx)
    h, (auxs, ys) = jax.lax.scan(body, x, params["layers"])
    return h, auxs.sum(), ys


def _hybrid_forward(cfg, ctx, params, x, positions, *, remat, collect_cache):
    k, n_full, tail = _hybrid_groups(cfg)
    scfg = _shared_cfg(cfg)
    shared = params["shared"]
    lp_all = params["layers"]
    lp_main = jax.tree.map(
        lambda a: a[:n_full * k].reshape((n_full, k) + a.shape[1:]), lp_all)
    lp_tail = jax.tree.map(lambda a: a[n_full * k:], lp_all)

    def shared_block(h):
        if collect_cache:
            h, (kk, vv) = L.attn_forward(scfg, ctx, shared["attn"], h,
                                         positions, return_kv=True)
        else:
            h = L.attn_forward(scfg, ctx, shared["attn"], h, positions)
            kk = vv = ()
        h = L.mlp_forward(scfg, ctx, shared["mlp"], h)
        return h, (kk, vv)

    def group(h, glp):
        h, kv = shared_block(h)
        h, ys = _mamba_scan(cfg, ctx, glp, h, remat=remat,
                            collect=collect_cache)
        return h, (kv, ys)

    if remat:
        group = _remat(group, ctx)
    h, (kvs, inner) = jax.lax.scan(group, x, lp_main)
    if tail:
        h, (kv_t, ys_t) = group(h, lp_tail)
        if collect_cache:
            kvs = jax.tree.map(
                lambda a, b: jnp.concatenate([a, b[None]], 0), kvs, kv_t)
            # inner ys: (n_full, k, ...) + tail (tail, ...) -> flat (L, ...)
            inner = jax.tree.map(
                lambda a, b: jnp.concatenate(
                    [a.reshape((-1,) + a.shape[2:]), b], 0), inner, ys_t)
    elif collect_cache:
        inner = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), inner)
    aux = jnp.zeros((), jnp.float32)
    if not collect_cache:
        return h, aux, ()
    st, tx, tbc = inner
    kk, vv = kvs
    return h, aux, {"ssm": st, "conv_x": tx, "conv_bc": tbc, "k": kk, "v": vv}


def embed_inputs(cfg: ModelConfig, ctx: ShardCtx, params, batch):
    tokens = batch["tokens"]
    x = L.embed_tokens(cfg, ctx, params["embed"], tokens)
    if cfg.modality == "vision" and "patch_embeds" in batch:
        pe = batch["patch_embeds"].astype(x.dtype)
        x = jnp.concatenate([pe, x[:, pe.shape[1]:]], axis=1)
    return x, jnp.arange(x.shape[1])


def loss_forward(cfg: ModelConfig, ctx: ShardCtx, params, batch, *,
                 remat: bool = True):
    x, positions = embed_inputs(cfg, ctx, params, batch)
    h, aux, _ = stack_forward(cfg, ctx, params, x, positions, remat=remat)
    s, c = L.lm_loss(cfg, ctx, params["embed"], h, batch["labels"])
    return s, c, aux


# --------------------------------------------------------------------------
# training step (microbatched grad accumulation + optimizer)


def _axes_in_spec(spec: P):
    used = set()
    for dim in spec:
        if dim is None:
            continue
        for ax in (dim,) if isinstance(dim, str) else tuple(dim):
            used.add(ax)
    return used


# --------------------------------------------------------------------------
# ZeRO-1: shard Adam m/v over the dp axes on each parameter's LAST dim


def zero1_plan(cfg: ModelConfig, ctx: ShardCtx, pspecs, params_abs):
    """Tree of bools: which leaves get dp-sharded optimizer state.

    A leaf qualifies when its LOCAL last dim divides dp_size and no dp axis
    already appears in its spec (FSDP leaves are naturally sharded)."""
    flat_p = jax.tree.leaves(params_abs)
    flat_s = jax.tree.leaves(pspecs, is_leaf=lambda x: isinstance(x, P))
    plan = []
    for p, sp in zip(flat_p, flat_s):
        ok = False
        if p.ndim >= 1 and p.size >= ctx.dp_size:
            used = _axes_in_spec(sp)
            if not any(ax in used for ax in ctx.dp_axes):
                last = tuple(sp)[-1] if len(sp) >= p.ndim else None
                tp_div = ctx.tp_size if (last == ctx.tp_axis or
                                         (isinstance(last, tuple)
                                          and ctx.tp_axis in last)) else 1
                local_last = p.shape[-1] // tp_div
                ok = local_last % ctx.dp_size == 0 and local_last > 0
        plan.append(ok)
    return jax.tree.unflatten(jax.tree.structure(params_abs), plan)


def zero1_opt_specs(cfg: ModelConfig, ctx: ShardCtx, pspecs, params_abs):
    """PartitionSpecs for Adam m/v under ZeRO-1."""
    plan = zero1_plan(cfg, ctx, pspecs, params_abs)
    flat_p = jax.tree.leaves(params_abs)
    flat_s = jax.tree.leaves(pspecs, is_leaf=lambda x: isinstance(x, P))
    flat_ok = jax.tree.leaves(plan)
    out = []
    dp = tuple(ctx.dp_axes) if len(ctx.dp_axes) > 1 else ctx.dp_axes[0]
    for p, sp, ok in zip(flat_p, flat_s, flat_ok):
        if not ok:
            out.append(sp)
            continue
        dims = list(tuple(sp)) + [None] * (p.ndim - len(tuple(sp)))
        last = dims[-1]
        if last is None:
            dims[-1] = dp
        elif isinstance(last, str):
            dims[-1] = (last,) + tuple(ctx.dp_axes)
        else:
            dims[-1] = tuple(last) + tuple(ctx.dp_axes)
        out.append(P(*dims))
    return jax.tree.unflatten(jax.tree.structure(params_abs), out)


def make_train_step(cfg: ModelConfig, ctx: ShardCtx, opt: Optimizer,
                    num_microbatches: int = 1, *, loss_fwd=None, specs=None,
                    zero1=None):
    """Microbatched grad-accumulation train step (LOCAL, inside shard_map).

    ``loss_fwd(params, batch) -> (sum_loss, count, aux)`` defaults to the
    decoder-LM loss; encdec passes its own. ``specs`` must match the param
    tree (used for cross-replica grad reductions and the global grad-norm).
    ``zero1``: bool tree from zero1_plan — Adam m/v arrive dp-sharded on the
    last dim; grads/params are sliced to match, updates all-gathered back.
    """
    if loss_fwd is None:
        loss_fwd = lambda p, b: loss_forward(cfg, ctx, p, b)
    if specs is None:
        specs = param_specs(cfg, ctx)
    flat_specs = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    dp_all = tuple(ctx.dp_axes)

    def _dp_idx():
        idx = jnp.zeros((), jnp.int32)
        for ax in dp_all:
            idx = idx * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
        return idx

    def z_slice(tree):
        if zero1 is None:
            return tree
        def f(x, ok):
            if not ok:
                return x
            chunk = x.shape[-1] // ctx.dp_size
            return jax.lax.dynamic_slice_in_dim(
                x, _dp_idx() * chunk, chunk, axis=x.ndim - 1)
        return jax.tree.map(f, tree, zero1)

    def z_gather(tree):
        if zero1 is None:
            return tree
        def f(x, ok):
            if not ok:
                return x
            return jax.lax.all_gather(x, dp_all, axis=x.ndim - 1, tiled=True)
        return jax.tree.map(f, tree, zero1)

    def train_step(params, opt_state, batch):
        nm = num_microbatches

        def split_mb(x):
            return x.reshape((nm, x.shape[0] // nm) + x.shape[1:])

        mb = jax.tree.map(split_mb, batch)
        count = (batch["labels"] >= 0).sum()
        count_global = jax.lax.psum(count, ctx.dp_axes) \
            if ctx.dp_size > 1 else count
        denom = jnp.maximum(count_global, 1).astype(jnp.float32)

        def loss_fn(p, b):
            s, c, aux = loss_fwd(p, b)
            return s / denom + AUX_COEF * aux / nm, s

        def micro(carry, b):
            g_acc, s_acc = carry
            (_, s), g = jax.value_and_grad(loss_fn, has_aux=True)(params, b)
            g_acc = jax.tree.map(lambda a, x: a + x.astype(jnp.float32),
                                 g_acc, g)
            return (g_acc, s_acc + s), None

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (grads, loss_sum), _ = jax.lax.scan(
            micro, (g0, jnp.zeros((), jnp.float32)), mb)

        # Cross-replica gradient reduction: each param's grad is reduced
        # over every dp axis its spec does NOT shard it along. (FSDP-stored
        # params were already reduce-scattered over their storage axis by
        # the all_gather VJP inside the layer.)
        flat_g, tdef = jax.tree.flatten(grads)
        red = []
        for g, sp in zip(flat_g, flat_specs):
            axes = tuple(ax for ax in ctx.dp_axes
                         if ax not in _axes_in_spec(sp))
            red.append(jax.lax.psum(g, axes) if axes else g)
        grads = jax.tree.unflatten(tdef, red)

        # Global grad-norm: shard-local squared norms of SHARDED leaves are
        # partial sums and must be psummed over the axes in their spec;
        # replicated leaves contribute once. Doing this correctly keeps the
        # clip scale identical on every device (otherwise replicated params
        # would desync across tp shards).
        sq_by_axes: Dict[tuple, Any] = {}
        flat_g2 = jax.tree.leaves(grads)
        for g, sp in zip(flat_g2, flat_specs):
            axes = tuple(sorted(_axes_in_spec(sp) & set((ctx.tp_axis,)
                                                        + tuple(ctx.dp_axes))))
            sq_by_axes[axes] = sq_by_axes.get(axes, 0.0) + jnp.vdot(g, g).real
        total = jnp.zeros((), jnp.float32)
        for axes, val in sq_by_axes.items():
            total = total + (jax.lax.psum(val, axes) if axes else val)
        gnorm = jnp.sqrt(total + 1e-12)
        scale = jnp.minimum(1.0, cfg.max_grad_norm / (gnorm + 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)
        updates, opt_state = opt.update(z_slice(grads), opt_state,
                                        z_slice(params))
        params = apply_updates(params, z_gather(updates))
        loss_total = jax.lax.psum(loss_sum, ctx.dp_axes) \
            if ctx.dp_size > 1 else loss_sum
        metrics = {"loss": loss_total / denom, "gnorm": gnorm}
        return params, opt_state, metrics

    return train_step


# --------------------------------------------------------------------------
# KV / state cache


def init_cache(cfg: ModelConfig, ctx: ShardCtx, global_batch: int,
               seq_len: int, *, prefilled: bool = False):
    """GLOBAL cache arrays (zeros). ``prefilled`` marks index=seq_len (for
    dry-run decode inputs the values are placeholders anyway)."""
    mode = L.decode_mode(cfg, ctx, global_batch, seq_len)
    dt = jnp.dtype(cfg.dtype)
    hd = cfg.hd
    B = global_batch
    idx0 = seq_len if prefilled else 0
    cache: Dict[str, Any] = {"index": jnp.asarray(idx0, jnp.int32)}
    kind = _block_kind(cfg)
    n_inv = n_shared_invocations(cfg)
    s_c = mode["s_cache"]
    kvh = cfg.num_kv_heads

    quant = getattr(ctx, "kv_int8", False)

    def kv_arrays(n_layers):
        kdt = jnp.int8 if quant else dt
        kk = jnp.zeros((n_layers, B, s_c, kvh, hd), kdt)
        return kk, jnp.zeros_like(kk)

    def scale_arrays(n_layers):
        sc = jnp.zeros((n_layers, B, s_c, kvh, 1), jnp.float32)
        return sc, jnp.zeros_like(sc)

    if kind in ("dense", "moe"):
        cache["k"], cache["v"] = kv_arrays(cfg.num_layers)
        if quant:
            cache["k_scale"], cache["v_scale"] = scale_arrays(cfg.num_layers)
        cache["pos"] = jnp.full((s_c,), -1, jnp.int32)
    else:
        H, Pd, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
        gn2 = 2 * cfg.ssm_groups * cfg.ssm_state
        cache["ssm"] = jnp.zeros((cfg.num_layers, B, H, Pd, N), jnp.float32)
        cache["conv_x"] = jnp.zeros(
            (cfg.num_layers, B, cfg.ssm_conv - 1, cfg.d_inner), dt)
        cache["conv_bc"] = jnp.zeros(
            (cfg.num_layers, B, cfg.ssm_conv - 1, gn2), dt)
        if n_inv:
            cache["k"], cache["v"] = kv_arrays(n_inv)
            cache["pos"] = jnp.full((s_c,), -1, jnp.int32)
    return cache


def cache_specs(cfg: ModelConfig, ctx: ShardCtx, global_batch: int,
                seq_len: int):
    mode = L.decode_mode(cfg, ctx, global_batch, seq_len)
    dp = tuple(ctx.dp_axes) if len(ctx.dp_axes) > 1 else ctx.dp_axes[0]
    b_ax = dp if mode["batch_dp"] else None
    kind = _block_kind(cfg)
    specs: Dict[str, Any] = {"index": P()}
    seq_axes = mode["seq_axes"]
    s_ax = None
    if seq_axes:
        s_ax = tuple(seq_axes) if len(seq_axes) > 1 else seq_axes[0]
    kv_sharded_in_cache = (mode["kind"] in ("A", "W")
                           and cfg.num_kv_heads % ctx.tp_size == 0)
    kv_ax = ctx.tp_axis if kv_sharded_in_cache else None
    kv_spec = P(None, b_ax, s_ax, kv_ax, None)
    if kind in ("dense", "moe"):
        specs["k"] = kv_spec
        specs["v"] = kv_spec
        if getattr(ctx, "kv_int8", False):
            specs["k_scale"] = kv_spec
            specs["v_scale"] = kv_spec
        specs["pos"] = P(s_ax)
    else:
        tp = ctx.tp_axis
        specs["ssm"] = P(None, b_ax, tp, None, None)
        specs["conv_x"] = P(None, b_ax, None, tp)
        specs["conv_bc"] = P(None, b_ax, None, None)
        if n_shared_invocations(cfg):
            specs["k"] = kv_spec
            specs["v"] = kv_spec
            specs["pos"] = P(s_ax)
    return specs


# --------------------------------------------------------------------------
# prefill step


def make_prefill(cfg: ModelConfig, ctx: ShardCtx, global_batch: int,
                 seq_len: int):
    mode = L.decode_mode(cfg, ctx, global_batch, seq_len)
    kind = _block_kind(cfg)

    def pack_kv(k, v, S_):
        """k/v: (Linv, B, S, KV?, hd) local -> cache layout + pos array."""
        s_c = mode["s_cache"]
        if mode["kind"] == "W":
            keepn = min(s_c, S_)
            pos = jnp.arange(S_ - keepn, S_)
            slots = pos % s_c
            def ring(a):
                out = jnp.zeros(a.shape[:2] + (s_c,) + a.shape[3:], a.dtype)
                return out.at[:, :, slots].set(a[:, :, S_ - keepn:])
            posarr = jnp.full((s_c,), -1, jnp.int32).at[slots].set(pos)
            return ring(k), ring(v), posarr
        pad = s_c - S_
        kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        posarr = jnp.concatenate([jnp.arange(S_, dtype=jnp.int32),
                                  jnp.full((pad,), -1, jnp.int32)])
        if mode["seq_axes"]:
            n = L.axes_size(ctx, mode["seq_axes"])
            s_loc = s_c // n
            st = L._axes_index(ctx, mode["seq_axes"]) * s_loc
            kp = jax.lax.dynamic_slice_in_dim(kp, st, s_loc, axis=2)
            vp = jax.lax.dynamic_slice_in_dim(vp, st, s_loc, axis=2)
            posarr = jax.lax.dynamic_slice_in_dim(posarr, st, s_loc, axis=0)
        return kp, vp, posarr

    def prefill(params, batch):
        x, positions = embed_inputs(cfg, ctx, params, batch)
        h, _, ys = stack_forward(cfg, ctx, params, x, positions,
                                 collect_cache=True)
        logits = L.lm_logits_last(cfg, ctx, params["embed"], h[:, -1])
        S_ = x.shape[1]
        cache: Dict[str, Any] = {"index": jnp.asarray(S_, jnp.int32)}
        if kind in ("dense", "moe"):
            k, v = ys
            if getattr(ctx, "kv_int8", False):
                kq, ks = L.kv_quantize(k)
                vq, vs = L.kv_quantize(v)
                cache["k"], cache["v"], cache["pos"] = pack_kv(kq, vq, S_)
                cache["k_scale"], cache["v_scale"], _ = pack_kv(ks, vs, S_)
            else:
                cache["k"], cache["v"], cache["pos"] = pack_kv(k, v, S_)
        elif cfg.family == "hybrid":
            cache.update(ssm=ys["ssm"], conv_x=ys["conv_x"],
                         conv_bc=ys["conv_bc"])
            cache["k"], cache["v"], cache["pos"] = pack_kv(
                ys["k"], ys["v"], S_)
        else:
            st, tx, tbc = ys
            cache.update(ssm=st, conv_x=tx, conv_bc=tbc)
        return logits, cache

    return prefill


# --------------------------------------------------------------------------
# decode step


def make_decode(cfg: ModelConfig, ctx: ShardCtx, global_batch: int,
                seq_len: int):
    mode = L.decode_mode(cfg, ctx, global_batch, seq_len)
    kind = _block_kind(cfg)
    scfg = _shared_cfg(cfg) if cfg.family == "hybrid" else None

    def decode(params, cache, token):
        index = cache["index"]
        x = L.embed_tokens(cfg, ctx, params["embed"], token)  # (B, 1, d)
        new_cache = dict(cache)

        if kind in ("dense", "moe"):
            quant = getattr(ctx, "kv_int8", False)

            def body(carry, xs):
                h, pos = carry
                if quant:
                    lp, kc, vc, ksc, vsc = xs
                    h, kc, vc, pos, ksc, vsc = L.attn_decode(
                        cfg, ctx, lp["attn"], h, kc, vc, pos, index, mode,
                        k_scale=ksc, v_scale=vsc)
                else:
                    lp, kc, vc = xs
                    h, kc, vc, pos = L.attn_decode(
                        cfg, ctx, lp["attn"], h, kc, vc, pos, index, mode)
                if kind == "moe":
                    h, _ = M.moe_forward(cfg, ctx, lp["moe"], h)
                else:
                    h = L.mlp_forward(cfg, ctx, lp["mlp"], h)
                ys = (kc, vc, ksc, vsc) if quant else (kc, vc)
                return (h, pos), ys

            if quant:
                (h, pos), (ks, vs, kscs, vscs) = jax.lax.scan(
                    body, (x, cache["pos"]),
                    (params["layers"], cache["k"], cache["v"],
                     cache["k_scale"], cache["v_scale"]))
                new_cache.update(k=ks, v=vs, pos=pos, k_scale=kscs,
                                 v_scale=vscs)
            else:
                (h, pos), (ks, vs) = jax.lax.scan(
                    body, (x, cache["pos"]),
                    (params["layers"], cache["k"], cache["v"]))
                new_cache.update(k=ks, v=vs, pos=pos)
        elif cfg.family == "hybrid":
            kk, n_full, tail = _hybrid_groups(cfg)
            shared = params["shared"]
            lp_all = params["layers"]
            lp_main = jax.tree.map(
                lambda a: a[:n_full * kk].reshape((n_full, kk) + a.shape[1:]),
                lp_all)
            lp_tail = jax.tree.map(lambda a: a[n_full * kk:], lp_all)
            st_all, tx_all, tbc_all = (cache["ssm"], cache["conv_x"],
                                       cache["conv_bc"])
            def reshape_main(a):
                return a[:n_full * kk].reshape((n_full, kk) + a.shape[1:])
            def mamba_group(h, glp, gst, gtx, gtbc):
                def inner(carry, xs):
                    h = carry
                    lp, st, tx, tbc = xs
                    h, st, tx, tbc = S.mamba_decode(
                        cfg, ctx, lp["mamba"], h, st, tx, tbc)
                    return h, (st, tx, tbc)
                return jax.lax.scan(inner, h, (glp, gst, gtx, gtbc))

            def group(carry, xs):
                h, pos = carry
                glp, kc, vc, gst, gtx, gtbc = xs
                h, kc, vc, pos = L.attn_decode(
                    scfg, ctx, shared["attn"], h, kc, vc, pos, index, mode)
                h = L.mlp_forward(scfg, ctx, shared["mlp"], h)
                h, states = mamba_group(h, glp, gst, gtx, gtbc)
                return (h, pos), ((kc, vc), states)

            n_inv = n_shared_invocations(cfg)
            k_main = cache["k"][:n_full]
            v_main = cache["v"][:n_full]
            (h, pos), ((ks, vs), states) = jax.lax.scan(
                group, (x, cache["pos"]),
                (lp_main, k_main, v_main,
                 reshape_main(st_all), reshape_main(tx_all),
                 reshape_main(tbc_all)))
            sts, txs, tbcs = states  # (n_full, kk, ...)
            flat = lambda a: a.reshape((-1,) + a.shape[2:])
            sts, txs, tbcs = flat(sts), flat(txs), flat(tbcs)
            if tail:
                (h, pos), ((kt, vt), st_t) = group(
                    (h, pos),
                    (lp_tail, cache["k"][n_full], cache["v"][n_full],
                     st_all[n_full * kk:], tx_all[n_full * kk:],
                     tbc_all[n_full * kk:]))
                ks = jnp.concatenate([ks, kt[None]], 0)
                vs = jnp.concatenate([vs, vt[None]], 0)
                sts = jnp.concatenate([sts, st_t[0]], 0)
                txs = jnp.concatenate([txs, st_t[1]], 0)
                tbcs = jnp.concatenate([tbcs, st_t[2]], 0)
            new_cache.update(ssm=sts, conv_x=txs, conv_bc=tbcs,
                             k=ks, v=vs, pos=pos)
        else:  # pure ssm
            def body(carry, xs):
                h = carry
                lp, st, tx, tbc = xs
                h, st, tx, tbc = S.mamba_decode(
                    cfg, ctx, lp["mamba"], h, st, tx, tbc)
                return h, (st, tx, tbc)

            h, (sts, txs, tbcs) = jax.lax.scan(
                body, x, (params["layers"], cache["ssm"], cache["conv_x"],
                          cache["conv_bc"]))
            new_cache.update(ssm=sts, conv_x=txs, conv_bc=tbcs)

        logits = L.lm_logits_last(cfg, ctx, params["embed"], h[:, 0])
        new_cache["index"] = index + 1
        return logits, new_cache

    return decode


# --------------------------------------------------------------------------
# per-slot cache (continuous-batching serve tier)
#
# The lock-step decode above shares ONE scalar ``index`` and one (S,)
# ``pos`` across the whole batch — every request must start and stop
# together. The serve tier instead treats each batch row as an
# independent SLOT at its own position, so requests stream through a
# single compiled decode program (repro.serve).


def _slot_mode(cfg: ModelConfig, ctx: ShardCtx, n_slots: int,
               seq_len: int):
    """decode_mode restricted to the layouts the serve tier supports:
    attention KV families, fp cache, kind "A", no sliding window. When
    the slot count does not divide dp the cache is replicated instead of
    seq-sharded (serve keeps state batch-resident)."""
    if _block_kind(cfg) not in ("dense", "moe"):
        raise ValueError(
            f"serve tier needs an attention KV cache; family "
            f"{cfg.family!r} has none (ssm/hybrid state is lock-step only)")
    if cfg.attn_window:
        raise ValueError("serve tier does not support sliding-window "
                         "(ring) caches")
    if getattr(ctx, "kv_int8", False):
        raise ValueError("serve tier does not support int8 KV caches")
    mode = L.decode_mode(cfg, ctx, n_slots, seq_len)
    if mode["kind"] != "A":
        raise ValueError(
            f"serve tier needs a kind-'A' cache (num_kv_heads divisible "
            f"by tp), got kind {mode['kind']!r}")
    if mode["seq_axes"]:
        mode = dict(mode, seq_axes=(), s_cache=seq_len + 1)
    return mode


def init_cache_slots(cfg: ModelConfig, ctx: ShardCtx, n_slots: int,
                     seq_len: int):
    """GLOBAL slot-pool cache (all slots empty): per-slot ``index`` (B,)
    token counts and ``pos`` (B, s_cache) position maps (-1 empty)."""
    mode = _slot_mode(cfg, ctx, n_slots, seq_len)
    dt = jnp.dtype(cfg.dtype)
    s_c = mode["s_cache"]
    k = jnp.zeros((cfg.num_layers, n_slots, s_c, cfg.num_kv_heads, cfg.hd),
                  dt)
    return {"index": jnp.zeros((n_slots,), jnp.int32),
            "k": k, "v": jnp.zeros_like(k),
            "pos": jnp.full((n_slots, s_c), -1, jnp.int32)}


def cache_specs_slots(cfg: ModelConfig, ctx: ShardCtx, n_slots: int,
                      seq_len: int):
    mode = _slot_mode(cfg, ctx, n_slots, seq_len)
    dp = tuple(ctx.dp_axes) if len(ctx.dp_axes) > 1 else ctx.dp_axes[0]
    b_ax = dp if mode["batch_dp"] else None
    kv_ax = ctx.tp_axis if cfg.num_kv_heads % ctx.tp_size == 0 else None
    kv_spec = P(None, b_ax, None, kv_ax, None)
    return {"index": P(b_ax), "k": kv_spec, "v": kv_spec,
            "pos": P(b_ax, None)}


def make_prefill_slots(cfg: ModelConfig, ctx: ShardCtx, global_batch: int,
                       seq_len: int):
    """Prefill one serve admission bucket (fixed shapes, per-row prompt
    lengths). Two differences from make_prefill make right-padded prompts
    decode correctly: logits come from each row's LAST REAL token
    (``prompt_len - 1``, not ``seq_len - 1``), and cache positions at and
    after the prompt are marked empty (-1) so the padding's KV is never
    attended. Causality already keeps the real tokens' KV independent of
    the padding to their right."""
    mode = _slot_mode(cfg, ctx, global_batch, seq_len)

    def prefill(params, batch, prompt_len):
        x, positions = embed_inputs(cfg, ctx, params, batch)
        h, _, (k, v) = stack_forward(cfg, ctx, params, x, positions,
                                     collect_cache=True)
        S_ = x.shape[1]
        last = jnp.clip(prompt_len - 1, 0, S_ - 1)
        h_last = jnp.take_along_axis(h, last[:, None, None], axis=1)[:, 0]
        logits = L.lm_logits_last(cfg, ctx, params["embed"], h_last)
        s_c = mode["s_cache"]
        pad = s_c - S_
        kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        posarr = jnp.arange(s_c, dtype=jnp.int32)[None, :]
        posarr = jnp.where(posarr < prompt_len[:, None], posarr, -1)
        cache = {"index": prompt_len.astype(jnp.int32),
                 "k": kp, "v": vp, "pos": posarr}
        return logits, cache

    return prefill


def make_decode_slots(cfg: ModelConfig, ctx: ShardCtx, n_slots: int,
                      seq_len: int):
    """Continuous-batching decode: ONE new token for every ACTIVE slot.

    ``cache`` is the slot-pool layout of :func:`init_cache_slots`,
    ``token`` is (n_slots, 1) pending tokens and ``active`` is (n_slots,)
    bool. Inactive slots are computed but never written (drop-mode
    scatter), so admissions and retirements between calls never change
    shapes — the step compiles exactly once per (n_slots, seq_len)."""
    mode = _slot_mode(cfg, ctx, n_slots, seq_len)
    kind = _block_kind(cfg)

    def decode(params, cache, token, active):
        index = cache["index"]
        x = L.embed_tokens(cfg, ctx, params["embed"], token)

        def body(carry, xs):
            h, pos = carry
            lp, kc, vc = xs
            h, kc, vc, pos = L.attn_decode_slots(
                cfg, ctx, lp["attn"], h, kc, vc, pos, index, active, mode)
            if kind == "moe":
                h, _ = M.moe_forward(cfg, ctx, lp["moe"], h)
            else:
                h = L.mlp_forward(cfg, ctx, lp["mlp"], h)
            return (h, pos), (kc, vc)

        (h, pos), (ks, vs) = jax.lax.scan(
            body, (x, cache["pos"]),
            (params["layers"], cache["k"], cache["v"]))
        logits = L.lm_logits_last(cfg, ctx, params["embed"], h[:, 0])
        new_cache = dict(cache, k=ks, v=vs, pos=pos,
                         index=index + active.astype(jnp.int32))
        return logits, new_cache

    return decode
