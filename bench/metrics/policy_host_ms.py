"""Host time of one policy step (the program's ``policy.step`` span:
the version-gated model pull, the improvement's dispatch and the policy
push, not the device's work), mean over the spans whole inside the
traced window."""


def read(ctx):
    from harness import spans
    return spans.mean_ms(ctx, "policy.step")
