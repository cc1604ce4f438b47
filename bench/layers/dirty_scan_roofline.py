"""The pre-copy dirty scan's share of its roofline.

A scan reads the live state and its shadow once: 2 x state bytes, at the
chip's HBM bandwidth the least time of one scan (its few operations per
element are far below the chip's peak, so bytes bound it). A scan runs one program
per leaf (``precopy._leaf_dirty`` with ``kernels/dirty_delta.py``, traced
as ``jit__leaf_dirty``), so the scans in the window are the program's runs
over the leaves; the ``bench.migrate`` spans carry ``state_bytes`` and
``leaves``. The share is the least time of those scans over their device
time.
"""

SPAN = "bench.migrate"
PROGRAM = "jit__leaf_dirty"


def work(state_bytes: float) -> float:
    """Bytes one scan must read."""
    return 2.0 * state_bytes


def read(run):
    if run.trace is None:
        return None
    spans = run.trace.spans_named(SPAN)
    secs, runs = run.trace.module_time(PROGRAM)
    if not spans or not runs or secs <= 0:
        return None
    scans = runs / spans[0].args["leaves"]
    least = scans * work(spans[0].args["state_bytes"]) \
        / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / secs
