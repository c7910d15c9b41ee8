"""Per-kernel correctness: Pallas (interpret=True) vs pure-jnp oracle,
swept over shapes and dtypes, plus chunked-reference self-consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.flash_attention.pallas import flash_attention
from repro.kernels.gmm import ref as gmm_ref
from repro.kernels.gmm.pallas import grouped_matmul
from repro.kernels.ssd import ref as ssd_ref
from repro.kernels.ssd.pallas import ssd_chunked

KEY = jax.random.key(42)


def rand(shape, dtype, i, scale=1.0):
    return (jax.random.normal(jax.random.fold_in(KEY, i), shape)
            * scale).astype(dtype)


# ------------------------------------------------------------ attention
ATT_CASES = [
    # B, Sq, Sk, Hq, Hkv, D, causal, window
    (2, 128, 128, 4, 2, 64, True, 0),
    (1, 96, 96, 4, 4, 32, True, 0),
    (2, 256, 256, 8, 2, 64, True, 64),
    (1, 64, 64, 2, 2, 128, False, 0),
    (1, 64, 192, 4, 1, 64, True, 0),      # prefix cache (Sk > Sq)
]


@pytest.mark.parametrize("case", ATT_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_vs_oracle(case, dtype):
    B, Sq, Sk, Hq, Hkv, D, causal, win = case
    q = rand((B, Sq, Hq, D), dtype, 1)
    k = rand((B, Sk, Hkv, D), dtype, 2)
    v = rand((B, Sk, Hkv, D), dtype, 3)
    out = flash_attention(q, k, v, causal=causal, window=win, interpret=True)
    exp = fa_ref.naive_attention(q, k, v, causal=causal, window=win)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("case", ATT_CASES[:3])
def test_chunked_ref_matches_naive(case):
    B, Sq, Sk, Hq, Hkv, D, causal, win = case
    q = rand((B, Sq, Hq, D), jnp.float32, 4)
    k = rand((B, Sk, Hkv, D), jnp.float32, 5)
    v = rand((B, Sk, Hkv, D), jnp.float32, 6)
    out = fa_ref.chunked_attention(q, k, v, causal=causal, window=win,
                                   block_q=32, block_k=64)
    exp = fa_ref.naive_attention(q, k, v, causal=causal, window=win)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-3,
                               rtol=2e-3)


def test_decode_partial_combine():
    """Sharded decode partials must combine to the unsharded answer."""
    B, S, Hq, Hkv, D = 2, 128, 4, 2, 64
    q = rand((B, Hq, D), jnp.float32, 7)
    k = rand((B, S, Hkv, D), jnp.float32, 8)
    v = rand((B, S, Hkv, D), jnp.float32, 9)
    length = 100
    o_full, _ = fa_ref.decode_attention_partial(q, k, v, length)
    outs, lses = [], []
    for sh in range(4):
        ks = k[:, sh * 32:(sh + 1) * 32]
        vs = v[:, sh * 32:(sh + 1) * 32]
        o, l = fa_ref.decode_attention_partial(q, ks, vs, length,
                                               start=sh * 32)
        outs.append(o)
        lses.append(l)
    comb = fa_ref.combine_partials(jnp.stack(outs), jnp.stack(lses))
    np.testing.assert_allclose(np.asarray(comb), np.asarray(o_full),
                               atol=1e-5, rtol=1e-5)


# ------------------------------------------------------------------ ssd
SSD_CASES = [
    (2, 256, 4, 32, 16, 1, 64),
    (1, 100, 8, 16, 32, 2, 32),
    (2, 64, 4, 64, 64, 1, 64),
    (1, 128, 2, 32, 8, 1, 128),
]


@pytest.mark.parametrize("case", SSD_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_pallas_vs_oracle(case, dtype):
    B, L, H, P, N, G, chunk = case
    x = rand((B, L, H, P), dtype, 10, 0.5)
    dt = jax.nn.softplus(rand((B, L, H), jnp.float32, 11))
    A = -jnp.exp(rand((H,), jnp.float32, 12, 0.3))
    Bm = rand((B, L, G, N), dtype, 13, 0.3)
    C = rand((B, L, G, N), dtype, 14, 0.3)
    out = ssd_chunked(x, dt, A, Bm, C, chunk=chunk, interpret=True)
    exp = ssd_ref.ssd_chunked(x, dt, A, Bm, C, chunk=chunk)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-3
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               atol=tol, rtol=tol)


def test_ssd_chunked_matches_sequential():
    B, L, H, P, N, G = 2, 160, 4, 16, 8, 1
    x = rand((B, L, H, P), jnp.float32, 15, 0.5)
    dt = jax.nn.softplus(rand((B, L, H), jnp.float32, 16))
    A = -jnp.exp(rand((H,), jnp.float32, 17, 0.3))
    Bm = rand((B, L, G, N), jnp.float32, 18, 0.3)
    C = rand((B, L, G, N), jnp.float32, 19, 0.3)
    for chunk in (32, 64, 160):
        out = ssd_ref.ssd_chunked(x, dt, A, Bm, C, chunk=chunk)
        exp = ssd_ref.ssd_sequential(x, dt, A, Bm, C)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   atol=1e-3, rtol=1e-3)


def test_ssd_decode_matches_scan_tail():
    """Recurrent decode steps continue exactly from a chunked prefill."""
    B, L, H, P, N, G = 1, 96, 4, 16, 8, 1
    x = rand((B, L + 4, H, P), jnp.float32, 20, 0.5)
    dt = jax.nn.softplus(rand((B, L + 4, H), jnp.float32, 21))
    A = -jnp.exp(rand((H,), jnp.float32, 22, 0.3))
    Bm = rand((B, L + 4, G, N), jnp.float32, 23, 0.3)
    C = rand((B, L + 4, G, N), jnp.float32, 24, 0.3)
    y_full = ssd_ref.ssd_sequential(x, dt, A, Bm, C)
    y_pre, state = ssd_ref.ssd_chunked(x[:, :L], dt[:, :L], A, Bm[:, :L],
                                       C[:, :L], chunk=32,
                                       return_final_state=True)
    for t in range(L, L + 4):
        y_t, state = ssd_ref.ssd_decode_step(
            state, x[:, t], dt[:, t], A, Bm[:, t], C[:, t])
        np.testing.assert_allclose(np.asarray(y_t), np.asarray(y_full[:, t]),
                                   atol=1e-3, rtol=1e-3)


# ------------------------------------------------------------------ gmm
@pytest.mark.parametrize("dims", [(4, 64, 32, 48), (2, 200, 130, 70),
                                  (8, 16, 16, 16), (1, 128, 128, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gmm_pallas_vs_oracle(dims, dtype):
    G, M, K, N = dims
    a = rand((G, M, K), dtype, 25)
    b = rand((G, K, N), dtype, 26)
    out = grouped_matmul(a, b, interpret=True)
    exp = gmm_ref.grouped_matmul(a, b)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol,
                               rtol=tol)


# ----------------------------------------------------------- ragged gmm
RAGGED_CASES = [
    # G, M, K, N, group_sizes — empty, full, uneven, tile-straddling
    (4, 64, 32, 48, (10, 0, 54, 0)),
    (3, 200, 130, 70, (200, 0, 0)),
    (5, 37, 16, 16, (5, 8, 0, 20, 4)),
    (1, 128, 128, 128, (128,)),
    (3, 300, 96, 40, (1, 298, 1)),
]


@pytest.mark.parametrize("case", RAGGED_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ragged_gmm_pallas_vs_oracle(case, dtype):
    G, M, K, N, sizes = case
    assert sum(sizes) == M
    gs = jnp.array(sizes, jnp.int32)
    a = rand((M, K), dtype, 27)
    b = rand((G, K, N), dtype, 28)
    out = grouped_matmul(a, b, gs, interpret=True)
    exp = gmm_ref.grouped_matmul(a, b, gs)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol,
                               rtol=tol)


@pytest.mark.parametrize("case", RAGGED_CASES[:3])
def test_ragged_oracle_matches_per_group_numpy(case):
    """The ragged oracle itself against the plainest possible spelling:
    slice each group out and np.dot it."""
    G, M, K, N, sizes = case
    gs = jnp.array(sizes, jnp.int32)
    a = rand((M, K), jnp.float32, 29)
    b = rand((G, K, N), jnp.float32, 30)
    out = np.asarray(gmm_ref.grouped_matmul(a, b, gs))
    an, bn = np.asarray(a), np.asarray(b)
    off = 0
    for g, sz in enumerate(sizes):
        exp = an[off:off + sz] @ bn[g]
        np.testing.assert_allclose(out[off:off + sz], exp, atol=1e-4,
                                   rtol=1e-4)
        off += sz


def test_ragged_gmm_jits_with_traced_sizes():
    """group_sizes is data (bincount of sampled members) — the ragged
    path must trace with it as a dynamic operand."""
    G, M, K, N = 3, 48, 16, 24
    a = rand((M, K), jnp.float32, 31)
    b = rand((G, K, N), jnp.float32, 32)
    gs = jnp.array([20, 0, 28], jnp.int32)
    out = jax.jit(gmm_ref.grouped_matmul)(a, b, gs)
    exp = gmm_ref.grouped_matmul(a, b, gs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-5)


def test_ensemble_mlp_select_impls_agree():
    """dense (compute-all-and-select), ref (sort/ragged/unsort) and
    pallas-interpret must produce the same per-row member outputs."""
    from repro.kernels.gmm import ops as gmm_ops
    K_, B, Din, Dh, Dout = 4, 33, 7, 24, 5
    members = {
        "w": [rand((K_, Din, Dh), jnp.float32, 33),
              rand((K_, Dh, Dout), jnp.float32, 34)],
        "b": [rand((K_, Dh), jnp.float32, 35),
              rand((K_, Dout), jnp.float32, 36)],
    }
    x = rand((B, Din), jnp.float32, 37)
    for idx in (jnp.zeros((B,), jnp.int32),               # one full group
                jnp.full((B,), K_ - 1, jnp.int32),        # last group only
                jax.random.randint(jax.random.fold_in(KEY, 38), (B,), 0,
                                   K_)):
        dense = gmm_ops.ensemble_mlp_select(members, x, idx, impl="dense")
        exp = jnp.take_along_axis(gmm_ref.ensemble_mlp(members, x),
                                  idx[None, :, None], axis=0)[0]
        np.testing.assert_array_equal(np.asarray(dense), np.asarray(exp))
        ref_out = gmm_ops.ensemble_mlp_select(members, x, idx, impl="ref")
        np.testing.assert_allclose(np.asarray(ref_out), np.asarray(exp),
                                   atol=1e-5, rtol=1e-5)
        pk_out = gmm_ops.ensemble_mlp_select(members, x, idx,
                                             impl="pallas", interpret=True)
        np.testing.assert_allclose(np.asarray(pk_out), np.asarray(exp),
                                   atol=1e-4, rtol=1e-4)


def test_moe_dropless_matches_capacity_path():
    """Dropless ragged dispatch must agree with the capacity-buffer path
    when capacity is generous enough that nothing drops."""
    from repro.models import moe as MOE
    from repro.models.config import ModelConfig, ShardCtx
    cfg = ModelConfig(name="t", family="moe", num_layers=1, d_model=16,
                      num_heads=2, num_kv_heads=2, d_ff=32, vocab_size=64,
                      num_experts=4, top_k=2, capacity_factor=8.0,
                      dtype="float32")
    ctx = ShardCtx()
    p = MOE.init_moe(cfg, ctx, jax.random.key(0))
    x = rand((2, 8, 16), jnp.float32, 40, 0.5)
    y_cap, aux_cap = MOE.moe_forward(cfg, ctx, p, x)   # capacity (CPU gate)
    y_drop, aux_drop = MOE.moe_forward_dropless(cfg, p, x)
    np.testing.assert_allclose(np.asarray(y_drop), np.asarray(y_cap),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(float(aux_drop), float(aux_cap), rtol=1e-5)


# ------------------------------------------------- hypothesis properties
from _hypothesis_compat import given, settings, st


@settings(max_examples=15, deadline=None)
@given(sq=st.integers(4, 80), hq=st.sampled_from([1, 2, 4]),
       g=st.sampled_from([1, 2]), d=st.sampled_from([16, 32]))
def test_attention_causality_property(sq, hq, g, d):
    """Changing FUTURE tokens never changes past outputs (causality)."""
    hkv = max(hq // g, 1)
    hq = hkv * g
    q = rand((1, sq, hq, d), jnp.float32, sq)
    k = rand((1, sq, hkv, d), jnp.float32, sq + 1)
    v = rand((1, sq, hkv, d), jnp.float32, sq + 2)
    out1 = fa_ref.chunked_attention(q, k, v, causal=True, block_q=16,
                                    block_k=16)
    k2 = k.at[:, -1].add(10.0)
    v2 = v.at[:, -1].add(10.0)
    out2 = fa_ref.chunked_attention(q, k2, v2, causal=True, block_q=16,
                                    block_k=16)
    np.testing.assert_allclose(np.asarray(out1[:, :-1]),
                               np.asarray(out2[:, :-1]), atol=1e-5)


@settings(max_examples=10, deadline=None)
@given(l=st.integers(8, 60), h=st.sampled_from([1, 2, 4]),
       p=st.sampled_from([8, 16]), n=st.sampled_from([4, 8]))
def test_ssd_causality_property(l, h, p, n):
    x = rand((1, l, h, p), jnp.float32, l, 0.5)
    dt = jax.nn.softplus(rand((1, l, h), jnp.float32, l + 1))
    A = -jnp.exp(rand((h,), jnp.float32, l + 2, 0.3))
    Bm = rand((1, l, 1, n), jnp.float32, l + 3, 0.3)
    C = rand((1, l, 1, n), jnp.float32, l + 4, 0.3)
    y1 = ssd_ref.ssd_chunked(x, dt, A, Bm, C, chunk=16)
    x2 = x.at[:, -1].add(5.0)
    y2 = ssd_ref.ssd_chunked(x2, dt, A, Bm, C, chunk=16)
    np.testing.assert_allclose(np.asarray(y1[:, :-1]),
                               np.asarray(y2[:, :-1]), atol=1e-5)
