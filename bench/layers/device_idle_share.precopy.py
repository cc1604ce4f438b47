"""Share of the traced window in which no operation ran on the chip, in the
pre-copy cell: 100 * (1 - busy / window), busy being the union of the device
operations' intervals (``bench/trace.py``)."""


def read(run):
    if run.trace is None or run.trace.window_s <= 0 or not run.trace.busy:
        return None
    return 100.0 * run.trace.idle_share
