"""Roofline analysis from the dry-run's compiled artifacts.

Three terms per (arch x shape x mesh), in seconds per step, against the
per-chip peaks of the device the dry-run targets (``PEAKS``, keyed by
jax's ``device_kind``; the production meshes are v5e pods):

  t_compute    = FLOPs_per_device / peak bf16 FLOP/s
  t_memory     = HBM_bytes_per_device / peak HBM bytes/s
  t_collective = collective_bytes_per_device / ICI bytes/s per link

Collective bytes come from the compiled HLO (parsed + while-loop trip
scaling in repro.launch.dryrun.collective_bytes) — the real artifact.
FLOPs and HBM bytes are ANALYTIC: XLA's cost_analysis() counts while-loop
bodies once (verified experimentally — see EXPERIMENTS.md §Roofline), so
scan-over-layers programs would be undercounted ~L x; the analytic model
below is exact for the dense algebra we emit and is cross-checked against
cost_analysis x trip-count on a no-scan variant.

useful_flop_frac = MODEL_FLOPS / FLOPs_total where MODEL_FLOPS = 6·N·D
(train, dense), 6·N_active·D (MoE) or 2·N_active per decoded token —
the gap exposes remat recompute, attention quadratics and pad-head waste.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List

# Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819
# GB/s HBM, 1,600 Gbit/s interconnect = 4 links of 50 GB/s).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "ici_bytes_per_s_per_link": 50e9},
}
# the dry-run compiles for launch/mesh.py's v5e pod meshes
DRYRUN_DEVICE_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> Dict[str, float]:
    """Peaks of one chip of ``device_kind``; an unknown kind is an
    error, never a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; add them to PEAKS with a source")
    return PEAKS[device_kind]

F32, BF16 = 4, 2


def _chips(mesh: str) -> int:
    return 512 if mesh == "2x16x16" else 256


def _cfg(arch: str, shape: str):
    from repro.configs import registry
    return registry.get_config(arch, long_context=(shape == "long_500k"))


def _attn_flops_fwd(cfg, B, S, causal=True) -> float:
    """2 matmuls (qk + av), 2 flops/MAC, causal halves the square."""
    if cfg.family == "ssm":
        return _ssd_flops_fwd(cfg, B, S)
    hd = cfg.hd
    H = cfg.num_heads
    window = cfg.attn_window
    kv_span = min(S, window) if window else S
    per_layer = 2 * 2 * B * S * kv_span * H * hd * (0.5 if causal and
                                                    not window else 1.0)
    n_attn = cfg.num_layers
    if cfg.family == "hybrid":
        n_attn = (cfg.num_layers + cfg.attn_every - 1) // cfg.attn_every
        per_layer += 0  # mamba layers counted via _ssd_flops
        return per_layer * n_attn + _ssd_flops_fwd(cfg, B, S)
    if cfg.family == "encdec":
        # decoder self (causal) + cross (full) + encoder self (full)
        enc = 2 * 2 * B * S * S * H * hd
        cross = 2 * 2 * B * S * S * H * hd
        return per_layer * cfg.num_layers + (enc + cross) * cfg.num_layers
    return per_layer * n_attn


def _ssd_flops_fwd(cfg, B, S) -> float:
    """Intra-chunk quadratic + state flops per the SSD algorithm."""
    if cfg.family not in ("ssm", "hybrid"):
        return 0.0
    H, P, N, Q = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_chunk
    nc = S // max(Q, 1)
    intra = 2 * B * nc * (Q * Q * H * N + Q * Q * H * P)   # CB^T then ·x
    states = 2 * B * nc * (Q * H * P * N) * 2              # build + apply
    return (intra + states) * cfg.num_layers


def flops_per_step(cfg, shape_kind, B, S, n_params, n_active) -> Dict:
    """Global analytic FLOPs for one step."""
    D = B * S
    if shape_kind == "train":
        base = 6 * n_active * D            # fwd 2ND + bwd 4ND on matmuls
        attn = 3 * _attn_flops_fwd(cfg, B, S)
        remat = 2 * n_active * D + _attn_flops_fwd(cfg, B, S)  # fwd recompute
        total = base + attn + remat
        model = 6 * n_active * D
    elif shape_kind == "prefill":
        total = 2 * n_active * D + _attn_flops_fwd(cfg, B, S)
        model = 2 * n_active * D
    else:  # decode: one token per sequence
        total = 2 * n_active * B
        # attention over the cache
        if cfg.family != "ssm":
            window = cfg.attn_window
            span = min(S, window) if window else S
            n_attn = cfg.num_layers if cfg.family != "hybrid" else \
                (cfg.num_layers + cfg.attn_every - 1) // cfg.attn_every
            total += 2 * 2 * B * span * cfg.num_heads * cfg.hd * n_attn
        if cfg.family in ("ssm", "hybrid"):
            total += 2 * B * cfg.ssm_heads * cfg.ssm_head_dim \
                * cfg.ssm_state * 3 * cfg.num_layers
        model = 2 * n_active * B
    return {"total": total, "model": model}


def hbm_bytes_per_device(cfg, shape_kind, B, S, n_params, chips, mesh,
                         num_microbatches) -> float:
    """Analytic per-device HBM traffic per step."""
    tp = 16
    dp = chips // tp
    p_loc = n_params / chips * chips / tp / (1 if True else 1)
    # params are sharded over tp only (dense) — FSDP archs shard more, but
    # use the tp-only bound (conservative upper estimate for them)
    p_loc_bytes = n_params / tp * BF16
    d_tokens_loc = B * S / dp
    d = cfg.d_model
    L = cfg.num_layers
    if shape_kind == "train":
        nm = max(num_microbatches, 1)
        weight_traffic = p_loc_bytes * nm * 3          # fwd + bwd + remat fwd
        opt_traffic = n_params / tp * (F32 * 2 * 2     # m, v read+write
                                       + F32 * 2      # grad read, param rw
                                       + BF16 * 2)
        act_traffic = d_tokens_loc * d * BF16 * L * 12  # ~6 tensors rw
        return weight_traffic + opt_traffic + act_traffic
    if shape_kind == "prefill":
        act = d_tokens_loc * d * BF16 * L * 8
        return p_loc_bytes + act
    # decode
    cache = 0.0
    if cfg.family in ("dense", "moe", "vlm", "encdec"):
        span = min(S, cfg.attn_window) if cfg.attn_window else S
        kv = max(cfg.num_kv_heads, 1)
        kv_loc = max(kv / tp, 1) if kv % tp == 0 else kv
        b_loc = max(B / dp, 1)
        cache = b_loc * span * kv_loc * cfg.hd * BF16 * 2 * L
        if cfg.family == "encdec":
            cache *= 2  # cross cache too
    if cfg.family in ("ssm", "hybrid"):
        b_loc = max(B / dp, 1)
        cache += b_loc * cfg.ssm_heads / tp * cfg.ssm_head_dim \
            * cfg.ssm_state * F32 * 2 * L
        if cfg.family == "hybrid":
            span = S
            n_inv = (L + cfg.attn_every - 1) // cfg.attn_every
            cache += (B * span / chips) * cfg.num_kv_heads * cfg.hd \
                * BF16 * 2 * n_inv
    active_loc = 0  # params read once
    return p_loc_bytes + cache


def one_sentence(bottleneck, cfg, shape_kind) -> str:
    if bottleneck == "collective":
        return ("psum traffic dominates: overlap/bucket the reductions, "
                "cast them to bf16, and avoid the conservative psum "
                "transpose (replication-checked shard_map)")
    if bottleneck == "memory":
        if shape_kind == "decode":
            return ("KV/state cache streaming bound: shrink cache dtype "
                    "(int8 KV), shard the cache further, or batch more "
                    "decode requests per weight read")
        return ("weight/activation streaming bound: raise arithmetic "
                "intensity with larger microbatches or fewer remat passes")
    return ("MXU-bound: increase overlap of collectives under compute and "
            "keep matmul dims 128-aligned — already near the good regime")


def roofline_table(records: List[dict]) -> List[dict]:
    from repro.models.config import INPUT_SHAPES
    pk = peaks(DRYRUN_DEVICE_KIND)
    rows = []
    for r in records:
        if not r.get("ok"):
            rows.append({**r, "ok": False})
            continue
        shape = INPUT_SHAPES[r["shape"]]
        cfg = _cfg(r["arch"], r["shape"])
        chips = _chips(r["mesh"])
        B, S = shape.global_batch, shape.seq_len
        fl = flops_per_step(cfg, shape.kind, B, S, r["params"],
                            r["active_params"])
        t_compute = fl["total"] / chips / pk["flops"]
        hbm = hbm_bytes_per_device(cfg, shape.kind, B, S, r["params"],
                                   chips, r["mesh"],
                                   r.get("num_microbatches", 1))
        t_memory = hbm / pk["hbm_bytes_per_s"]
        cc = r.get("collectives", {})
        if "ici_bytes" in cc:
            coll = cc["ici_bytes"]
        else:
            # ring-model approximation from per-type operand totals
            # (records written before the parser gained group awareness;
            # assumes 16-wide groups, exact for this mesh's tp/dp axes)
            g = 16
            coll = (2 * cc.get("all-reduce", 0) * (g - 1) / g
                    + cc.get("all-gather", 0) * (g - 1) / g
                    + cc.get("reduce-scatter", 0) * (g - 1)
                    + cc.get("all-to-all", 0) * (g - 1) / g
                    + cc.get("collective-permute", 0))
        t_coll = coll / pk["ici_bytes_per_s_per_link"]
        terms = {"compute": t_compute, "memory": t_memory,
                 "collective": t_coll}
        bottleneck = max(terms, key=terms.get)
        rows.append({
            "arch": r["arch"], "shape": r["shape"], "mesh": r["mesh"],
            "ok": True,
            "t_compute_ms": t_compute * 1e3,
            "t_memory_ms": t_memory * 1e3,
            "t_collective_ms": t_coll * 1e3,
            "bottleneck": bottleneck,
            "model_flops": fl["model"],
            "hlo_flops_body": r.get("cost_analysis", {}).get("flops"),
            "useful_flop_frac": round(fl["model"] / max(fl["total"], 1), 3),
            "collective_bytes": coll,
            "hbm_bytes_est": hbm,
            "fix_hint": one_sentence(bottleneck, cfg, shape.kind),
        })
    return rows


def main():
    path = Path(__file__).parent.parent / "dryrun_results.json"
    rows = roofline_table(json.loads(path.read_text()))
    hdr = (f"{'arch':24s} {'shape':12s} {'mesh':8s} {'comp_ms':>9s} "
           f"{'mem_ms':>9s} {'coll_ms':>9s} {'bound':>10s} {'useful':>7s}")
    print(hdr)
    for r in rows:
        if not r.get("ok"):
            continue
        print(f"{r['arch']:24s} {r['shape']:12s} {r['mesh']:8s} "
              f"{r['t_compute_ms']:9.2f} {r['t_memory_ms']:9.2f} "
              f"{r['t_collective_ms']:9.2f} {r['bottleneck']:>10s} "
              f"{r['useful_flop_frac']:7.3f}")
    out = Path(__file__).parent / "results" / "roofline.json"
    out.parent.mkdir(exist_ok=True)
    out.write_text(json.dumps(rows, indent=1))
    print("wrote", out)


if __name__ == "__main__":
    main()
