"""The benchmark harness: one run of one cell.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own that the harness finds by name:

- ``BENCHMARK.json`` (checkout root): cells, metrics and bounds;
- ``bench/configs/<config>.json``: the deployment, its sizes and the limits
  of its correctness comparison; its ``driver`` names the system path;
- ``bench/traffic/<mix>.json``: the parameters of the mix, read by the
  driver's general generator;
- ``bench/drivers/<driver>.py``: set-up, measured window and comparison of
  one system path. ``setup(config, traffic, seed, ctx)`` returns a cell
  with ``window(seconds)``, ``end_to_end()``, ``counters()``, ``release()``,
  ``readings(control)`` (the numbers the comparison reads, of the program
  or of the control) and ``tally()`` (answers attempted and failed);
- ``bench/layers/<metric>.py``: ``read(run)`` of one per-layer metric from
  the reduced device trace and the run's counters, ``None`` when there is
  nothing to read.

A run sets up (load, warm-up, every program compiled or loaded from the
compile cache), measures for the given seconds, frees the program's state,
runs the comparison, and prints the result as the last line of standard
output. It refuses to run without a TPU: no number it prints comes from a
CPU.
"""
from __future__ import annotations

import contextlib
import gc
import importlib
import importlib.util
import json
import pathlib
import shutil
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

ROOT = pathlib.Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".bench_cache" / "jax"
TRACE_DIR = ROOT / ".bench_trace"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest(root: pathlib.Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def find(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}")


def config_of(root: pathlib.Path, name: str) -> dict:
    return load_json(root / "bench" / "configs" / f"{name}.json")


def traffic_of(root: pathlib.Path, name: str) -> dict:
    return load_json(root / "bench" / "traffic" / f"{name}.json")


def driver_of(name: str):
    return importlib.import_module(f"bench.drivers.{name}")


def reader_of(root: pathlib.Path, metric: str) -> Callable:
    path = root / "bench" / "layers" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_layer_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(man: dict, cell: str, traced: bool) -> List[dict]:
    """The metrics a run of ``cell`` reports: its end-to-end metrics
    untraced, its per-layer metrics traced. A metric without a
    ``workloads`` list belongs to every cell (per-layer: every cell that
    reports the end-to-end metric it moves)."""
    def has(m: dict) -> bool:
        return "workloads" not in m or cell in m["workloads"]

    e2e = [m for m in man["end_to_end"] if has(m)]
    if not traced:
        return e2e
    names = {m["name"] for m in e2e}

    def belongs(m: dict) -> bool:
        if "workloads" in m:
            return cell in m["workloads"]
        return m["moves"] in names

    return [m for m in man["per_layer"] if belongs(m)]


def load_peaks(kind: str, root: pathlib.Path = ROOT) -> dict:
    table = load_json(root / "bench" / "peaks.json")
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table["devices"][kind]


class CompileCounter:
    """Counts XLA backend compiles (name, seconds) and persistent-cache
    hits, from ``jax.monitoring``. JAX times a program loaded from the
    persistent cache as a backend compile too; such a load is a hit, not a
    compile."""

    def __init__(self):
        import jax
        self.log: List[tuple] = []
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    @property
    def n(self) -> int:
        return len(self.log) - self.hits

    def _on_time(self, event: str, secs: float, **kw) -> None:
        if event == BACKEND_COMPILE:
            self.log.append((kw.get("fun_name", "?"), secs))

    def _on_event(self, event: str, **_) -> None:
        if event == CACHE_HIT:
            self.hits += 1


class HostClock:
    """What the host did during a step of a window. ``read()`` gives running
    totals, while the clock is installed (``with HostClock()``), of the
    process's CPU seconds, the seconds spent in Python's garbage collector,
    and the seconds by which a watchdog thread that wakes every 20 ms woke
    late: the process held off its CPU by other processes on the host (or
    by a thread that kept the interpreter's lock). A slow step is then told
    apart: the host computing, collecting, or held off; with none of these,
    waiting on the device."""

    TICK_S = 0.02

    def __init__(self):
        self.gc_s = 0.0
        self.late_s = 0.0
        self._gc_start = 0.0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._watch, daemon=True)

    def _on_gc(self, phase: str, _info) -> None:
        now = time.perf_counter()
        if phase == "start":
            self._gc_start = now
        else:
            self.gc_s += now - self._gc_start

    def _watch(self) -> None:
        due = time.perf_counter() + self.TICK_S
        while not self._stop.wait(max(0.0, due - time.perf_counter())):
            now = time.perf_counter()
            if now - due > 0.001:
                self.late_s += now - due
            due = now + self.TICK_S

    def read(self) -> tuple:
        """(cpu_s, gc_s, held_s), each a running total."""
        return time.process_time(), self.gc_s, self.late_s

    def __enter__(self) -> "HostClock":
        gc.callbacks.append(self._on_gc)
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join()
        gc.callbacks.remove(self._on_gc)

    @staticmethod
    def describe(name: str, wall_s: list, host: list) -> str:
        """One log line: the median step, and the slowest with what the
        host did in it. ``host``: per step, the differences of ``read()``."""
        import numpy as np
        i = int(np.argmax(wall_s))
        cpu, gcs, held = host[i]
        return (f"{name} n={len(wall_s)} median_s={np.median(wall_s)} "
                f"median_cpu_s={np.median([h[0] for h in host])} "
                f"slowest_s={wall_s[i]} at {i}: cpu_s={cpu} gc_s={gcs} "
                f"held_s={held} (window held_s={sum(h[2] for h in host)})")


@dataclass
class Ctx:
    """What a driver gets from the harness besides config and traffic."""
    tracing: bool = False
    log: Callable[[str], None] = lambda s: print(s, file=sys.stderr,
                                                  flush=True)

    def span(self, name: str, **args):
        """A host span in the profiler's trace, or nothing when the run is
        not traced."""
        if not self.tracing:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name, **args)


@dataclass
class Run:
    """What a per-layer reader is given."""
    trace: Any                    # bench.trace.Reduced, or None
    counters: Dict[str, float] = field(default_factory=dict)
    peaks: Dict[str, float] = field(default_factory=dict)


def require_chips(chips: int) -> Dict[str, Any]:
    """The device block of the result; raises NoChip without a TPU or with
    fewer chips than ``chips``."""
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        raise NoChip(f"JAX's default backend is {backend!r}; the benchmark "
                     "runs only on a TPU")
    devs = jax.devices()
    if len(devs) < chips:
        raise NoChip(f"{chips} chips asked for, {len(devs)} visible")
    from repro.kernels import backend as kb
    if kb.kernel_backend() != "tpu":
        raise NoChip(f"the kernel dispatch row is {kb.kernel_backend()!r}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def use_compile_cache(path: pathlib.Path = CACHE_DIR) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    of its own, keeping every program, however fast it compiled, and
    evicting none: a size limit set in the environment turns on an eviction
    that fails on entries written without one."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _peak_bytes(chips: int) -> int:
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def run_cell(cell: dict, config: dict, traffic: dict, *, seed: int,
             seconds: float, traced: bool, metrics: List[dict],
             t_start: float, control: bool = False,
             root: pathlib.Path = ROOT) -> Dict[str, Any]:
    """One run of ``cell``. Returns the result object that is printed.

    With ``control`` the comparison reads the cell's control (the plain
    reference put in the program's place, one precision below the
    configuration's) instead of the program's answers, through the same
    limits: a sound comparison then reports ``correct`` false."""
    import jax
    device = require_chips(cell["chips"])
    use_compile_cache()
    counter = CompileCounter()
    ctx = Ctx(tracing=traced)
    driver = driver_of(config["driver"])
    if traffic["driver"] != config["driver"]:
        raise ValueError(f"traffic {cell['traffic']!r} is for driver "
                         f"{traffic['driver']!r}, not {config['driver']!r}")
    the_cell = driver.setup(config, traffic, seed, ctx)
    setup_s = time.perf_counter() - t_start
    ctx.log(f"setup setup_s={setup_s} compiles={counter.n} "
            f"cache_hits={counter.hits} slowest="
            f"{sorted(counter.log, key=lambda e: -e[1])[:3]}")

    c0, h0 = counter.n, counter.hits
    trace_path = None
    if traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    try:
        with ctx.span("bench.window"):
            the_cell.window(seconds)
    finally:
        if traced:
            jax.profiler.stop_trace()
            trace_path = next(TRACE_DIR.glob("plugins/profile/*/*.xplane.pb"))
    window_compiles = counter.n - c0
    ctx.log(f"window compiles={window_compiles} cache_hits={counter.hits - h0}"
            f" programs={sorted({n for n, _ in counter.log[c0 + h0:]})}")
    device["memory_peak_bytes"] = _peak_bytes(cell["chips"])

    counters = dict(the_cell.counters(), compiles_in_window=window_compiles)
    breakdown = None
    if traced:
        from bench import trace as tr
        red = tr.reduce(trace_path, devices=cell["chips"])
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        run = Run(red, counters, load_peaks(device["kind"], root))
        values = {m["name"]: reader_of(root, m["name"])(run)
                  for m in metrics}
        breakdown = red.breakdown()
    else:
        values = dict(the_cell.end_to_end(), setup_s=setup_s)

    the_cell.release()
    nums = the_cell.readings(control)
    checks = {k: {"value": nums[k], "limit": float(v)}
              for k, v in config["limits"].items() if k in nums}
    attempted, failed = the_cell.tally()
    result: Dict[str, Any] = {
        "correct": bool(checks) and all(c["value"] <= c["limit"]
                                        for c in checks.values()),
        "attempted": attempted,
        "failed": failed,
        "metrics": {m["name"]: {"value": values[m["name"]],
                                "unit": m["unit"]}
                    for m in metrics if values.get(m["name"]) is not None},
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def main(argv: Optional[List[str]] = None,
         t_start: Optional[float] = None) -> int:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="compare the control in the program's place (for "
                    "setting limits; the benchmark's runs never set it)")
    args = ap.parse_args(argv)
    man = manifest()
    cell = find(man["workloads"], args.workload, "workload")
    try:
        result = run_cell(cell, config_of(ROOT, cell["config"]),
                          traffic_of(ROOT, cell["traffic"]), seed=args.seed,
                          seconds=args.seconds, traced=bool(args.trace),
                          metrics=cell_metrics(man, cell["name"],
                                               bool(args.trace)),
                          t_start=t_start, control=bool(args.control))
    except NoChip as e:
        print(f"no chip: {e}", file=sys.stderr, flush=True)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name} value={c['value']!r} limit={c['limit']!r} "
              f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
