"""Host time the model learner waits on the chip each epoch (the
program's ``model.val_wait`` span: the held-out loss read back to the
host, with the epoch still queued ahead of it), mean over the spans
whole inside the traced window."""


def read(ctx):
    from harness import spans
    return spans.mean_ms(ctx, "model.val_wait")
