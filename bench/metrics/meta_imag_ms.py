"""Device time of the imagination kernel's calls in one MB-MPO policy
improvement: every ``kernels/imag`` call inside an execution of
``jit__improve_impl``, summed, over the executions whole inside the
traced window.

None unless every such execution holds exactly 2 K H calls (K inner and
K outer rollouts of H steps, one call per member and horizon step): the
proof that the members' rollouts ran through the kernel. Calls are
counted whether or not another event starts inside them (XLA's own
custom calls, allocations of no duration, can), so a call is not lost
as it would be among the leaf operations.

The profiler can cut the execution in flight when it starts or stops
recording and still place the part it kept inside the window: a step
of half its length and fewer calls. Only the first and the last
execution can be so cut; one of them with fewer calls is left out, as
an execution that straddles the window's edge is. Any other count
anywhere reads None.

``read(ctx, outside=True)`` gives the rest of the same executions'
device time instead (``meta_grad_ms``)."""


def read(ctx, outside=False):
    from harness.layers import is_kernel
    c = ctx.config
    want = 2 * c["n_models"] * c["imagine_horizon"]
    ex = sorted(ctx.executions("jit__improve_impl"), key=lambda x: x.start)
    calls = [e for e in next(iter(ctx.ops.values()), []) if is_kernel(e)]
    per = [(x, [e for e in calls if x.start <= e.start and e.end <= x.end])
           for x in ex]
    for edge in (-1, 0):
        if per and len(per[edge][1]) < want:
            per.pop(edge)
    if not per or any(len(p) != want for _, p in per):
        return None
    imag = sum(e.dur for _, p in per for e in p)
    if outside:
        return 1e3 * (sum(x.dur for x, _ in per) - imag) / len(per)
    return 1e3 * imag / len(per)
