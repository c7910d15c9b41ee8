"""The model learner's share of the chip's peak: the operations of a
whole epoch on a full ring (forward and backward, and the held-out
loss), times its executions in the traced window, over the window and the
bf16 peak."""


def read(ctx):
    n = len(ctx.executions("jit__train_epoch"))
    if n == 0:
        return None
    ops = ctx.flops.model_epoch(ctx.config) * n
    return 100.0 * ops / ctx.window_s / ctx.peak["flops"]
