"""Share of its roofline that the power-spectrum program reaches.

Work is counted from each call's shapes (the ``bench.power_spectrum`` spans
carry ``rows`` and ``n``) as any implementation must do it: the real input
read once, the one-sided spectrum written once, in float32, and the
operations of a real FFT, 2.5 n log2 n per row. The least time is the
larger of operations over the chip's bf16 peak and bytes over its HBM
bandwidth; the share is that over the device time of the spectrum program
(``kernels/dft.py``, traced as ``jit__dft_power``).
"""
import math

SPAN = "bench.power_spectrum"
PROGRAM = "jit__dft_power"


def work(rows: int, n: int):
    """(operations, bytes) of one power spectrum of ``rows`` rows of ``n``."""
    flops = rows * 2.5 * n * math.log2(n)
    nbytes = 4 * rows * (n + n // 2 + 1)
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
        flops, nbytes = work(int(s.args["rows"]), int(s.args["n"]))
        least += max(flops / run.peaks["bf16_flops_per_s"],
                     nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / secs
