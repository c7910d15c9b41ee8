"""Host time of one evaluation (the program's ``policy.eval`` span: the
policy thread's one wait on the chip, for every step queued ahead of
the evaluation's rollout and the rollout itself), mean over the spans
whole inside the traced window."""


def read(ctx):
    from harness import spans
    return spans.mean_ms(ctx, "policy.eval")
