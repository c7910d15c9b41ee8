"""Device time of one MB-MPO policy improvement outside the imagination
kernel: the inner adaptation, the meta-gradient through it, Adam, and
the row gathers around the kernel. Read over the executions
``meta_imag_ms`` reads, and None where it reads nothing."""


def read(ctx):
    from harness import cells
    return cells.metric_reader("meta_imag_ms")(ctx, outside=True)
