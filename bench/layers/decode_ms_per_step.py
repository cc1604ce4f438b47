"""Device milliseconds per decode step of the migrated serving job (its
jitted ``serve_step``, traced as ``jit_serve_step``)."""

PROGRAM = "jit_serve_step"


def read(run):
    if run.trace is None:
        return None
    secs, runs = run.trace.module_time(PROGRAM)
    if not runs:
        return None
    return 1e3 * secs / runs
