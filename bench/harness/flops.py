"""Operations and bytes the algorithms need, computed from shapes.

The parts every algorithm shares are here; what one policy improvement
of an algorithm adds is its file's ``step_ops`` (``bench/algos/``).

Counts are of matrix products (2 operations a multiply-add), in float32
(4 bytes). Nothing recomputed is counted, and an ensemble imagination
row costs one member, as the algorithm samples one.
"""
from __future__ import annotations

from . import cells

F32 = 4


def _dims(c, policy: bool):
    if policy:
        return ([c["obs_dim"]] + [c["policy_hidden"]] * c["policy_depth"]
                + [c["act_dim"]])
    return ([c["obs_dim"] + c["act_dim"]]
            + [c["model_hidden"]] * c["model_depth"] + [c["obs_dim"]])


def macs(dims) -> int:
    """Multiply-adds of one row through an MLP of layer sizes ``dims``."""
    return sum(a * b for a, b in zip(dims[:-1], dims[1:]))


def weights(dims) -> int:
    return sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))


def imagine_rows(c) -> int:
    return c["imagine_batch"] * c["imagine_horizon"]


def imag_call(c) -> tuple:
    """(operations, bytes) of one fused imagination step over the batch:
    the policy head and one member's dynamics per row; the policy and
    every member's weights read once, states and noise in, next states,
    actions and pre-actions out."""
    pd, dd = _dims(c, True), _dims(c, False)
    B = c["imagine_batch"]
    ops = 2 * B * (macs(pd) + macs(dd))
    io = B * (c["obs_dim"] + c["act_dim"]) + B * (c["obs_dim"]
                                                  + 2 * c["act_dim"])
    byts = F32 * (weights(pd) + c["n_models"] * weights(dd) + io)
    return ops, byts


def imagination(c) -> int:
    """Operations of one imagined rollout over the batch."""
    return c["imagine_horizon"] * imag_call(c)[0]


def policy_pass(c) -> int:
    """Operations of one pass of one row through the policy."""
    return 2 * macs(_dims(c, True))


def policy_step(c) -> int:
    """One policy improvement, as the configuration's algorithm counts it
    (``bench/algos/<algo>.py::step_ops``)."""
    return cells.algorithm(c["algo"]).step_ops(c)


def model_batches(c) -> int:
    bs = c["model_train_batch"]
    rows = c["ring_trajs"] * c["horizon"]
    return min(max(rows // bs, 1), 64)


def model_epoch(c) -> int:
    """One epoch on a full ring: each minibatch forward and backward
    (6 operations a multiply-add), then the held-out loss (forward)."""
    K, dm = c["n_models"], macs(_dims(c, False))
    val_rows = max(c["ring_trajs"] * c["horizon"] // 4, 1)
    return (model_batches(c) * 6 * K * c["model_train_batch"] * dm
            + 2 * K * val_rows * dm)


def floor_time(calls, peak: dict) -> float:
    """The least time a chip can take for these calls: per call the larger
    of operations over peak and bytes over bandwidth."""
    return sum(max(o / peak["flops"], b / peak["hbm_bytes_per_s"])
               for o, b in calls)
