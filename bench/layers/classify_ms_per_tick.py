"""Device milliseconds per tick of the naive-Bayes classify program
(``characterize._nb_predict_lm``, traced as ``jit__nb_predict_lm``)."""

PROGRAM = "jit__nb_predict_lm"


def read(run):
    ticks = run.counters.get("ticks", 0)
    if run.trace is None or not ticks:
        return None
    secs, runs = run.trace.module_time(PROGRAM)
    if not runs:
        return None
    return 1e3 * secs / ticks
