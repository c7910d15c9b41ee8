"""Compile the main path's Pallas kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler, which is installed with jax, compiles
for a ``v5e:2x2`` topology that is described, not attached. This catches
what interpret mode cannot (tile alignment, VMEM limits, a kernel the
Mosaic lowering refuses, a missing autodiff rule) at the widths
``chip_smoke.py`` runs: Arm7 (obs 23, act 7), a K=5 ensemble of 2x512
MLPs, a 2x64 policy, imagination batch 4096 and model minibatch 256.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.gmm import ops as gmm_ops
from repro.kernels.imag import ops as imag_ops
from repro.mbrl import dynamics as DYN
from repro.mbrl import policy as PI

OBS, ACT, HIDDEN, K, PHID = 23, 7, 512, 5, 64
IMAG_B, TRAIN_B = 4096, 256


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but can never be read back without one
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _ensemble(sharding):
    cfg = DYN.EnsembleConfig(OBS, ACT, hidden=HIDDEN, n_models=K)
    return _spec(jax.eval_shape(
        lambda: DYN.init_ensemble(cfg, jax.random.key(0))), sharding)


def _policy(sharding):
    cfg = PI.PolicyConfig(OBS, ACT, hidden=PHID)
    return _spec(jax.eval_shape(
        lambda: PI.init_policy(cfg, jax.random.key(0))), sharding)


def _rows(sharding, n, d, dtype=jnp.float32):
    return jax.ShapeDtypeStruct((n, d), dtype, sharding=sharding)


def test_imag_forward_compiles_for_v5e(one_chip):
    ens, pol = _ensemble(one_chip), _policy(one_chip)
    midx = jax.ShapeDtypeStruct((IMAG_B,), jnp.int32, sharding=one_chip)
    fn = lambda e, p, s, eps, m: imag_ops.fused_step(
        e["members"], e["norm"], p, s, eps, m, impl="pallas")
    text = jax.jit(fn).lower(ens, pol, _rows(one_chip, IMAG_B, OBS),
                             _rows(one_chip, IMAG_B, ACT),
                             midx).compile().as_text()
    assert "tpu_custom_call" in text


def test_gmm_equal_group_forward_compiles_for_v5e(one_chip):
    ens = _ensemble(one_chip)
    fn = lambda e, x: gmm_ops.ensemble_mlp(e["members"], x, impl="pallas")
    text = jax.jit(fn).lower(
        ens, _rows(one_chip, TRAIN_B, OBS + ACT)).compile().as_text()
    assert "tpu_custom_call" in text


def test_gmm_ragged_forward_compiles_for_v5e(one_chip):
    ens = _ensemble(one_chip)
    midx = jax.ShapeDtypeStruct((IMAG_B,), jnp.int32, sharding=one_chip)
    fn = lambda e, x, m: gmm_ops.ensemble_mlp_select(e["members"], x, m,
                                                     impl="pallas")
    text = jax.jit(fn).lower(ens, _rows(one_chip, IMAG_B, OBS + ACT),
                             midx).compile().as_text()
    assert "tpu_custom_call" in text


def test_masked_mse_grad_compiles_for_v5e(one_chip, monkeypatch):
    """The model learner's gradient through the Pallas path: the step
    that raised inside pallas_call's transpose before the custom_vjp."""
    # the dispatcher asks the (CPU) backend; steer it to the TPU branch
    monkeypatch.setattr(gmm_ops, "_on_tpu", lambda: True)
    ens = _ensemble(one_chip)
    w = jax.ShapeDtypeStruct((TRAIN_B,), jnp.bool_, sharding=one_chip)
    text = jax.jit(jax.grad(DYN.masked_mse_loss)).lower(
        ens, _rows(one_chip, TRAIN_B, OBS), _rows(one_chip, TRAIN_B, ACT),
        _rows(one_chip, TRAIN_B, OBS), w).compile().as_text()
    assert "tpu_custom_call" in text
