"""Device time of one model epoch (the ring trainer's ``train_epoch``),
mean over its executions."""


def read(ctx):
    return ctx.program_ms("jit__train_epoch")
