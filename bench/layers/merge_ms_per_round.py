"""Device milliseconds per pre-copy round of the merge that copies the
dirty blocks onto the shadow (``precopy._leaf_merge``, one program per
leaf, traced as ``jit__leaf_merge``; the ``bench.migrate`` spans carry
``leaves``)."""

SPAN = "bench.migrate"
PROGRAM = "jit__leaf_merge"


def read(run):
    if run.trace is None:
        return None
    spans = run.trace.spans_named(SPAN)
    secs, runs = run.trace.module_time(PROGRAM)
    if not spans or not runs:
        return None
    rounds = runs / spans[0].args["leaves"]
    return 1e3 * secs / rounds
