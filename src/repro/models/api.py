"""Global entry points: shard_map + jit wrappers around the local steps.

``build(cfg, mesh, shape)`` returns a ``StepBundle`` with the jitted global
function plus abstract (ShapeDtypeStruct) inputs and NamedShardings — the
dry-run lowers ``bundle.fn`` against ``bundle.abstract_args`` without ever
allocating parameters.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import encdec as E
from repro.models import lm as LM
from repro.models.config import InputShape, ModelConfig, ShardCtx
from repro.optim.optimizers import adam
from repro.utils.jit_stats import trace_counted


def shard_ctx(mesh, *, fsdp: bool = False, rs_ag: bool = False,
              save_collectives: bool = False, bf16_grad_reduce: bool = False,
              remat_group: int = 0, ws_moe: bool = False,
              seq_shard_decode: bool = False) -> ShardCtx:
    names = tuple(mesh.axis_names)
    assert "model" in names, names
    dp_axes = tuple(n for n in names if n != "model")
    dp_size = 1
    for n in dp_axes:
        dp_size *= mesh.shape[n]
    tp_size = mesh.shape["model"]
    fsdp_axis = "data" if (fsdp and "data" in dp_axes
                           and mesh.shape["data"] > 1) else None
    return ShardCtx(dp_axes=dp_axes, tp_axis="model", dp_size=dp_size,
                    tp_size=tp_size, seq_shard_decode=seq_shard_decode,
                    fsdp_axis=fsdp_axis,
                    fsdp_size=mesh.shape["data"] if fsdp_axis else 1,
                    rs_ag=rs_ag, save_collectives=save_collectives,
                    bf16_grad_reduce=bf16_grad_reduce,
                    remat_group=remat_group, ws_moe=ws_moe)


def _dp_spec_axis(ctx: ShardCtx):
    return tuple(ctx.dp_axes) if len(ctx.dp_axes) > 1 else ctx.dp_axes[0]


def batch_struct(cfg: ModelConfig, shape: InputShape, ctx: ShardCtx):
    """Abstract batch + PartitionSpecs for train/prefill inputs."""
    B, S = shape.global_batch, shape.seq_len
    dp = _dp_spec_axis(ctx) if B % ctx.dp_size == 0 and B >= ctx.dp_size \
        else None
    tok = jax.ShapeDtypeStruct((B, S), jnp.int32)
    dt = jnp.dtype(cfg.dtype)
    batch, specs = {}, {}
    if cfg.family == "encdec":
        batch["enc_embeds"] = jax.ShapeDtypeStruct((B, S, cfg.d_model), dt)
        specs["enc_embeds"] = P(dp, None, None)
        batch["tokens"] = tok
        specs["tokens"] = P(dp, None)
    elif cfg.modality == "vision":
        n_patch = S // 8
        batch["patch_embeds"] = jax.ShapeDtypeStruct((B, n_patch, cfg.d_model),
                                                     dt)
        specs["patch_embeds"] = P(dp, None, None)
        batch["tokens"] = tok
        specs["tokens"] = P(dp, None)
    else:
        batch["tokens"] = tok
        specs["tokens"] = P(dp, None)
    if shape.kind == "train":
        batch["labels"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
        specs["labels"] = P(dp, None)
    return batch, specs


def pick_microbatches(cfg: ModelConfig, shape: InputShape, ctx: ShardCtx,
                      target_tokens: int = 8192) -> int:
    if shape.kind != "train":
        return 1
    if shape.microbatch:
        return shape.microbatch
    b_loc = max(shape.global_batch // ctx.dp_size, 1)
    want = max(1, (b_loc * shape.seq_len) // target_tokens)
    nm = 1
    for cand in range(1, b_loc + 1):
        if b_loc % cand == 0 and cand <= want:
            nm = cand
    return nm


@dataclasses.dataclass
class StepBundle:
    kind: str
    fn: Callable                    # jitted global step
    abstract_args: Tuple[Any, ...]  # ShapeDtypeStructs (pytrees)
    in_shardings: Tuple[Any, ...]
    out_shardings: Any
    ctx: ShardCtx
    cfg: ModelConfig
    shape: InputShape
    num_microbatches: int = 1


def _shard_map(fn, mesh, in_specs, out_specs, check_vma=False):
    """check_vma=True enables replication tracking, which turns psum
    transposes into communication-free pbroadcasts (§Perf iteration 1)."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def _ns(mesh, specs):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def _mod(cfg: ModelConfig):
    return E if cfg.family == "encdec" else LM


def build(cfg: ModelConfig, mesh, shape: InputShape, *, fsdp: bool = False,
          microbatch_tokens: int = 8192, rs_ag: bool = False,
          save_collectives: bool = False, bf16_grad_reduce: bool = False,
          remat_group: int = 0, ws_moe: bool = False, zero1: bool = False,
          kv_int8: bool = False,
          check_vma: bool = False) -> StepBundle:
    ctx = shard_ctx(mesh, fsdp=fsdp, rs_ag=rs_ag,
                    save_collectives=save_collectives,
                    bf16_grad_reduce=bf16_grad_reduce,
                    remat_group=remat_group,
                    ws_moe=ws_moe and shape.kind == "decode")
    if kv_int8 and shape.kind in ("decode", "prefill") \
            and cfg.family in ("dense", "vlm", "moe"):
        import dataclasses as _dc
        ctx = _dc.replace(ctx, kv_int8=True)
    cfg.validate(ctx)
    mod = _mod(cfg)
    pspecs = mod.param_specs(cfg, ctx)
    params_abs = jax.eval_shape(
        lambda k: mod.init_params(cfg, ctx, k),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    B, S = shape.global_batch, shape.seq_len
    dp = _dp_spec_axis(ctx) if B % ctx.dp_size == 0 and B >= ctx.dp_size \
        else None

    if shape.kind == "train":
        nm = pick_microbatches(cfg, shape, ctx, microbatch_tokens)
        opt = adam(cfg.lr)
        opt_abs = jax.eval_shape(opt.init, params_abs)
        zplan = None
        mv_specs = pspecs
        if zero1 and ctx.dp_size > 1:
            zplan = LM.zero1_plan(cfg, ctx, pspecs, params_abs)
            mv_specs = LM.zero1_opt_specs(cfg, ctx, pspecs, params_abs)
        opt_specs = type(opt_abs)(step=P(), mu=mv_specs, nu=mv_specs)
        batch_abs, bspecs = batch_struct(cfg, shape, ctx)
        if cfg.family == "encdec":
            loss_fwd = lambda p, b: E.loss_forward(cfg, ctx, p, b)
            local = LM.make_train_step(cfg, ctx, opt, nm, loss_fwd=loss_fwd,
                                       specs=pspecs, zero1=zplan)
        else:
            local = LM.make_train_step(cfg, ctx, opt, nm, specs=pspecs,
                                       zero1=zplan)
        in_specs = (pspecs, opt_specs, bspecs)
        out_specs = (pspecs, opt_specs, {"loss": P(), "gnorm": P()})
        gfn = _shard_map(local, mesh, in_specs, out_specs, check_vma)
        fn = jax.jit(gfn, in_shardings=_ns(mesh, in_specs),
                     out_shardings=_ns(mesh, out_specs), donate_argnums=(0, 1))
        return StepBundle("train", fn, (params_abs, opt_abs, batch_abs),
                          _ns(mesh, in_specs), _ns(mesh, out_specs), ctx, cfg,
                          shape, nm)

    if shape.kind == "prefill":
        batch_abs, bspecs = batch_struct(cfg, shape, ctx)
        local = mod.make_prefill(cfg, ctx, B, S)
        cspecs = mod.cache_specs(cfg, ctx, B, S)
        logits_spec = P(dp, None)
        in_specs = (pspecs, bspecs)
        out_specs = (logits_spec, cspecs)
        gfn = _shard_map(local, mesh, in_specs, out_specs, check_vma)
        fn = jax.jit(gfn, in_shardings=_ns(mesh, in_specs),
                     out_shardings=_ns(mesh, out_specs))
        return StepBundle("prefill", fn, (params_abs, batch_abs),
                          _ns(mesh, in_specs), _ns(mesh, out_specs), ctx, cfg,
                          shape)

    # decode: ONE new token against a seq_len-deep cache
    local = mod.make_decode(cfg, ctx, B, S)
    cache_abs = jax.eval_shape(
        functools.partial(mod.init_cache, cfg, ctx, B, S, prefilled=True))
    cspecs = mod.cache_specs(cfg, ctx, B, S)
    token_abs = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    tok_spec = P(dp, None)
    logits_spec = P(dp, None)
    in_specs = (pspecs, cspecs, tok_spec)
    out_specs = (logits_spec, cspecs)
    gfn = _shard_map(local, mesh, in_specs, out_specs, check_vma)
    fn = jax.jit(gfn, in_shardings=_ns(mesh, in_specs),
                 out_shardings=_ns(mesh, out_specs), donate_argnums=(1,))
    return StepBundle("decode", fn, (params_abs, cache_abs, token_abs),
                      _ns(mesh, in_specs), _ns(mesh, out_specs), ctx, cfg,
                      shape)


# --------------------------------------------------------------------------
# world-model plumbing: the predict_fn contract


def as_predict_fn(fn):
    """Pin ``fn`` to the world-model predict contract:
    ``predict(params, obs, act, key) -> next_obs`` with
    ``next_obs.shape == obs.shape``.

    This is the interface ``mbrl.algos.make_algo(predict_fn=...)`` swaps
    in for the ensemble fast path (and what the fused imagination step
    bypasses when ``predict_fn is None``). The wrapper checks the shape
    contract AT TRACE TIME — a world model that silently returns a
    different state layout fails at swap-in, not three layers deep in a
    rollout scan — and tags the callable (``is_predict_fn``) so engines
    can validate a handed-in model before wiring it to a worker."""

    @functools.wraps(fn)
    def predict(params, obs, act, key):
        out = fn(params, obs, act, key)
        if out.shape != obs.shape:
            raise ValueError(
                f"predict_fn contract: next_obs shape {out.shape} != "
                f"obs shape {obs.shape}")
        return out

    predict.is_predict_fn = True
    return predict


# --------------------------------------------------------------------------
# serve tier (repro.serve): cache growth + per-slot bundles


def grow_cache(cache, to_len: int):
    """Grow a decode KV cache's sequence capacity to ``to_len`` slots.

    Replaces the hand-rolled ``jnp.pad`` dance in the serving example:
    ``k``/``v`` (and int8 scales when present) gain zero slots on the
    sequence axis while ``pos`` gains EMPTY (-1) slots — a 0-padded pos
    would alias global position 0 and corrupt the attention mask, which
    is precisely the easy-to-miss bug this helper exists to prevent.
    Handles both the lock-step layout (pos ``(S,)``) and the serve
    slot-pool layout (pos ``(B, S)``). Returns a shallow copy; no-op
    values when already at ``to_len``.
    """
    if "k" not in cache or "pos" not in cache:
        raise ValueError("grow_cache needs an attention KV cache "
                         "(ssm/hybrid state caches have no seq capacity)")
    cur = cache["k"].shape[2]
    if to_len < cur:
        raise ValueError(f"grow_cache cannot shrink the cache "
                         f"({cur} -> {to_len})")
    pad = to_len - cur
    out = dict(cache)
    if pad == 0:
        return out
    for key in ("k", "v", "k_scale", "v_scale"):
        if key in cache:
            a = cache[key]
            out[key] = jnp.pad(a, ((0, 0),) * 2 + ((0, pad),)
                               + ((0, 0),) * (a.ndim - 3))
    p = cache["pos"]
    out["pos"] = jnp.pad(p, ((0, 0),) * (p.ndim - 1) + ((0, pad),),
                         constant_values=-1)
    return out


def build_serve_prefill(cfg: ModelConfig, mesh, global_batch: int,
                        seq_len: int, *, check_vma: bool = False
                        ) -> StepBundle:
    """Serve-tier prefill of ONE admission bucket at fixed shapes.

    ``bundle.fn(params, batch, prompt_len)`` -> (per-row last-REAL-token
    logits, slot-layout cache); ``prompt_len`` is (B,) int32 so shorter
    prompts right-pad into the bucket without retracing. ``fn`` is a
    TraceCounted jit: the serve tier asserts its compile-once-per-bucket
    invariant through ``utils.jit_stats``.
    """
    ctx = shard_ctx(mesh)
    cfg.validate(ctx)
    pspecs = LM.param_specs(cfg, ctx)
    params_abs = jax.eval_shape(
        lambda k: LM.init_params(cfg, ctx, k),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    B, S = global_batch, seq_len
    shape = InputShape(f"serve-prefill-{S}", S, B, "prefill")
    dp = _dp_spec_axis(ctx) if B % ctx.dp_size == 0 and B >= ctx.dp_size \
        else None
    batch_abs, bspecs = batch_struct(cfg, shape, ctx)
    local = LM.make_prefill_slots(cfg, ctx, B, S)
    cspecs = LM.cache_specs_slots(cfg, ctx, B, S)
    in_specs = (pspecs, bspecs, P(dp))
    out_specs = (P(dp, None), cspecs)
    gfn = _shard_map(local, mesh, in_specs, out_specs, check_vma)
    fn = trace_counted(gfn, in_shardings=_ns(mesh, in_specs),
                       out_shardings=_ns(mesh, out_specs))
    plen_abs = jax.ShapeDtypeStruct((B,), jnp.int32)
    return StepBundle("serve_prefill", fn,
                      (params_abs, batch_abs, plen_abs),
                      _ns(mesh, in_specs), _ns(mesh, out_specs), ctx, cfg,
                      shape)


def build_serve_decode(cfg: ModelConfig, mesh, n_slots: int, seq_len: int,
                       *, check_vma: bool = False) -> StepBundle:
    """Serve-tier continuous-batching decode: one compiled program at
    (n_slots, seq_len) forever; requests stream through it.

    ``bundle.fn(params, cache, token, active)`` -> (logits, cache');
    the cache is donated (ring-buffer style in-place churn). ``fn`` is a
    TraceCounted jit so the no-retrace-under-churn invariant is
    assertable.
    """
    ctx = shard_ctx(mesh)
    cfg.validate(ctx)
    pspecs = LM.param_specs(cfg, ctx)
    params_abs = jax.eval_shape(
        lambda k: LM.init_params(cfg, ctx, k),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    B, S = n_slots, seq_len
    shape = InputShape(f"serve-decode-{S}", S, B, "decode")
    dp = _dp_spec_axis(ctx) if B % ctx.dp_size == 0 and B >= ctx.dp_size \
        else None
    local = LM.make_decode_slots(cfg, ctx, B, S)
    cache_abs = jax.eval_shape(
        functools.partial(LM.init_cache_slots, cfg, ctx, B, S))
    cspecs = LM.cache_specs_slots(cfg, ctx, B, S)
    token_abs = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    active_abs = jax.ShapeDtypeStruct((B,), jnp.bool_)
    in_specs = (pspecs, cspecs, P(dp, None), P(dp))
    out_specs = (P(dp, None), cspecs)
    gfn = _shard_map(local, mesh, in_specs, out_specs, check_vma)
    fn = trace_counted(gfn, in_shardings=_ns(mesh, in_specs),
                       out_shardings=_ns(mesh, out_specs),
                       donate_argnums=(1,))
    return StepBundle("serve_decode", fn,
                      (params_abs, cache_abs, token_abs, active_abs),
                      _ns(mesh, in_specs), _ns(mesh, out_specs), ctx, cfg,
                      shape)
