"""Host time of one landing's push into the data server (the program's
``data.push`` span in ``DataServer.push_batch``: the batch sliced into
one trajectory per robot, then appended under the lock), mean over the
spans whole inside the traced window."""


def read(ctx):
    from harness import spans
    return spans.mean_ms(ctx, "data.push")
