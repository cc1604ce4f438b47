"""Record the TPU trace that the benchmark's tests read the program's spans
from (``bench/testdata/tpu_v5e_spans.xplane.pb``).

    python scripts/record_spans_trace.py --out <dir>

On one TPU: a 256-job decide plane (512-sample windows, the Table 3 load
with 4 s phases, so cycles of 12-16 samples) whose jobs join in two halves,
one tick apart; untraced, each half's first fit. Traced, inside a
``bench.window`` span as in a benchmark run: the next four ticks of one new
sample each, the middle two of which refit one half's stale jobs each
through the splice path, in two groups, one with a period refinement. The
same ticks run once on another engine first, so that nothing compiles in
the trace. Writes ``<dir>/tpu_v5e_spans.xplane.pb``; exits non-zero without
a TPU.
"""
import argparse
import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

JOBS, WINDOW, STEPS, PHASE_S, SEED = 256, 512, 4, 4.0, 1300000019


def joined(nb, rows):
    """(store, engine) over ``rows`` (steps, jobs, fields) after the ticks
    at which the two halves of the jobs join and are first fit."""
    from bench.gen import fleet as gen
    from repro.core.surveillance import SurveillanceEngine
    from repro.core.telemetry import FleetTelemetry
    store = FleetTelemetry(JOBS, capacity=WINDOW, fields=gen.FIELDS)
    for s in range(WINDOW):
        store.record_fleet(s, rows[s])
    engine = SurveillanceEngine()
    views = store.views()
    for half, step in ((range(JOBS // 2), WINDOW - 1),
                       (range(JOBS // 2, JOBS), WINDOW)):
        if step >= WINDOW:
            store.record_fleet(step, rows[step])
        for i in half:
            engine.register(f"job{i:03d}", views[i], nb, window=WINDOW)
        engine.tick(step)
    return store, engine


def ticks(store, engine, rows):
    """The ``STEPS`` ticks after both halves joined; their refits."""
    refits = []
    for s in range(WINDOW + 1, WINDOW + 1 + STEPS):
        store.record_fleet(s, rows[s])
        refits.append(engine.tick(s).refitted)
    return refits


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", required=True)
    out = pathlib.Path(ap.parse_args().out)
    import jax
    if jax.default_backend() != "tpu":
        print(f"no TPU: JAX's backend is {jax.default_backend()!r}",
              file=sys.stderr)
        return 3
    from bench.gen import fleet as gen
    from repro.core import characterize
    rows = gen.make_load("table3", JOBS, WINDOW + 1 + STEPS, seed=SEED,
                         phase_s=PHASE_S).transpose(1, 0, 2)
    nb = characterize.fit(*gen.nb_training_set(SEED))
    print("warm-up refits per tick", ticks(*joined(nb, rows), rows),
          flush=True)
    store, engine = joined(nb, rows)
    tmp = pathlib.Path(tempfile.mkdtemp())
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            refits = ticks(store, engine, rows)
    finally:
        jax.profiler.stop_trace()
    out.mkdir(parents=True, exist_ok=True)
    dest = out / "tpu_v5e_spans.xplane.pb"
    shutil.copy(next(tmp.glob("plugins/profile/*/*.xplane.pb")), dest)
    shutil.rmtree(tmp)
    print("traced refits per tick", refits, dest, dest.stat().st_size,
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
