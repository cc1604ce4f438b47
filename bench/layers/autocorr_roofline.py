"""Share of its roofline that the autocorrelation program reaches.

Work is counted from each call's shapes (the ``bench.autocorr_score`` spans
carry ``rows``, ``n`` and the candidate lags ``lag_lo`` .. ``lag_hi``) as
the plain algorithm does it: one dot product per row and lag, 2 (n - lag)
operations, the rows and lags read once and the (rows, lags) scores
written once, in float32. The least time is the larger of operations over
the chip's bf16 peak and bytes over its HBM bandwidth; the share is that
over the device time of the program (``kernels/autocorr.py``, traced as
``jit__autocorr_score``).
"""

SPAN = "bench.autocorr_score"
PROGRAM = "jit__autocorr_score"


def work(rows: int, n: int, lag_lo: int, lag_hi: int):
    """(operations, bytes) of scoring ``rows`` rows of ``n`` at every lag
    from ``lag_lo`` to ``lag_hi``."""
    lags = range(lag_lo, lag_hi + 1)
    flops = rows * sum(2 * max(n - p, 0) for p in lags)
    nbytes = 4 * (rows * n + len(lags) + rows * len(lags))
    return flops, nbytes


def read(run):
    if run.trace is None:
        return None
    calls = run.trace.spans_named(SPAN)
    secs, runs = run.trace.module_time(PROGRAM)
    if not calls or not runs or secs <= 0:
        return None
    least = 0.0
    for s in calls:
        flops, nbytes = work(int(s.args["rows"]), int(s.args["n"]),
                             int(s.args["lag_lo"]), int(s.args["lag_hi"]))
        least += max(flops / run.peaks["bf16_flops_per_s"],
                     nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / secs
