"""The policy learner's share of the chip's peak: the operations of a
whole policy step, counted from its shapes, times its executions in the
traced window, over the window and the bf16 peak."""


def read(ctx):
    n = len(ctx.executions("jit__improve_impl"))
    if n == 0:
        return None
    ops = ctx.flops.policy_step(ctx.config) * n
    return 100.0 * ops / ctx.window_s / ctx.peak["flops"]
