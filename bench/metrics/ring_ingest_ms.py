"""Host time of one drain into the model learner's ring (the program's
``ring.ingest`` span: ``ReplayBuffer.extend`` and the early-stop reset,
on a drain that moved data), mean over the spans whole inside the
traced window."""


def read(ctx):
    from harness import spans
    return spans.mean_ms(ctx, "ring.ingest")
