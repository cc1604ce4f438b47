"""XLA backend compiles during the measured window (``jax.monitoring``); a
program loaded from the persistent compile cache does not count."""


def read(run):
    n = run.counters.get("compiles_in_window")
    return None if n is None else float(n)
