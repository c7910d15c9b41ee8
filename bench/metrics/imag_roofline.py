"""The fused imagination kernel's share of its roofline.

Every ``kernels/imag`` call inside a policy improvement: the least time
the chip could take for one imagination step over the batch (operations
over the bf16 peak, or bytes over the bandwidth, whichever is larger;
``flops.imag_call``) over the call's device time, summed over calls."""


def read(ctx):
    calls = ctx.kernels(("jit__improve_impl",))
    if not calls:
        return None
    floor = ctx.flops.floor_time([ctx.flops.imag_call(ctx.config)],
                                 ctx.peak)
    return 100.0 * floor * len(calls) / sum(e.dur for e in calls)
