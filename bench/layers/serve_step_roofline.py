"""Share of its roofline that the migrated job's decode step reaches.

A decode step of the hybrid replica must at the least read every weight
once, read the K and V of every filled position of each attention layer
once, and read and write the recurrent state (each Mamba layer's conv window
and SSM state) once; its operations, two per weight and token, are far
below the chip's peak at this batch, so bytes bound it. The ``bench.decode``
spans carry ``weight_bytes``, ``kv_position_bytes`` (K and V of one
position over the attention layers and requests), ``positions`` (filled
after the step) and ``recurrent_bytes``. The least time of a step is those
bytes at the chip's HBM bandwidth; the share is its mean over the spans
against the mean device time of the step program (``jit_serve_step``).
"""

SPAN = "bench.decode"
PROGRAM = "jit_serve_step"


def work(weight_bytes: float, kv_position_bytes: float, positions: float,
         recurrent_bytes: float) -> float:
    """Bytes one decode step must move."""
    return weight_bytes + kv_position_bytes * positions + 2 * recurrent_bytes


def read(run):
    if run.trace is None:
        return None
    steps = [s for s in run.trace.spans_named(SPAN) if "weight_bytes" in s.args]
    secs, runs = run.trace.module_time(PROGRAM)
    if not steps or not runs or secs <= 0:
        return None
    least = sum(work(s.args["weight_bytes"], s.args["kv_position_bytes"],
                     s.args["positions"], s.args["recurrent_bytes"])
                for s in steps) / len(steps) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (secs / runs)
