"""Device time of one farm step's batched rollout (the collector's
``rollout_batch`` jit), mean over its executions in the traced window.

The program is a ``jit__lambda``, a name it shares with the evaluation
rollout; it is told apart by its operations, which carry the farm's
lanes (robots per collector) by the observation width."""


def read(ctx):
    lanes = ctx.cell.traffic["robots_per_collector"]
    mark = f"f32[{lanes},{ctx.config['obs_dim']}]"
    runs = ctx.executions("jit__lambda")
    for name in sorted({e.name for e in runs}):
        ex = [e for e in runs if e.name == name]
        if any(mark in op.name for op in ctx.inside(ctx.leaf_ops(), [name],
                                                    whole_name=True)):
            return 1e3 * sum(e.dur for e in ex) / len(ex)
    return None
