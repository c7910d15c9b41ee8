"""Device time of one policy improvement (``MEAlgo._improve``:
imagination and the TRPO or PPO update), mean over its executions."""


def read(ctx):
    return ctx.program_ms("jit__improve_impl")
