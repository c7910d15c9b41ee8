"""Host time of one parameter push (the program's ``param.push`` span in
``ParameterServer.push``: the snapshot's copy dispatched and the version
bumped), mean over the spans whole inside the traced window, from both
learners."""


def read(ctx):
    from harness import spans
    return spans.mean_ms(ctx, "param.push")
