"""The ensemble kernel's share of its roofline in the model learner.

Every ``kernels/gmm`` call inside a ``train_epoch`` or the held-out
loss: the least time the chip could take for it (its operations over
the bf16 peak, or its bytes over the bandwidth, whichever is larger;
both from the shapes of its operands and result) over its device time,
summed over the calls."""


def read(ctx):
    from harness.layers import shapes
    calls = ctx.kernels(("jit__train_epoch", "jit__val_loss"))
    if not calls:
        return None
    floor = 0.0
    for e in calls:
        out, lhs, rhs = shapes(e)[:3]
        ops = 2 * _numel(out) * lhs[-1]
        byts = 4 * (_numel(out) + _numel(lhs) + _numel(rhs))
        floor += ctx.flops.floor_time([(ops, byts)], ctx.peak)
    return 100.0 * floor / sum(e.dur for e in calls)


def _numel(shape):
    n = 1
    for d in shape:
        n *= d
    return n
