"""``correct`` comes out true for a sound run, and false when the timed path
is broken underneath or when the control stands in the program's place.

Each cell's comparison runs here end to end through ``harness.run_cell`` at
a size a CPU holds, with the harness's look for a chip patched out: the
decide plane with 64 jobs, the pre-copy with a two-layer decoder of the
same kind. The limits are the cells' own, from their configuration files.
Faults, one at a time:

- a step that returns its state unchanged (no refit; no merge);
- half of the batch left out (half the stale jobs refit; half the blocks
  scanned);
- an answer or a token altered where it is produced.

One chip runs each cell, so no exchange between chips can be left out.
"""
import contextlib
import pathlib
import sys
import time

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import harness  # noqa: E402

SEED = 3141592653
MAN = harness.manifest()
DECIDE = harness.find(MAN["workloads"], "fleet-10k.steady-table3", "workload")
PRECOPY = harness.find(MAN["workloads"], "replica-internlm2-1p8b.decode",
                       "workload")


@pytest.fixture(autouse=True)
def cpu_device(monkeypatch):
    """These runs are on the CPU: the look for a TPU, the table of TPU
    peaks and the persistent compile cache (whose CPU programs may not suit
    another host) are patched out."""
    import jax

    def device(chips):
        return {"platform": jax.default_backend(),
                "kind": jax.devices()[0].device_kind,
                "count": len(jax.devices())}
    monkeypatch.setattr(harness, "require_chips", device)
    monkeypatch.setattr(harness, "load_peaks", lambda kind, root=None: {})
    monkeypatch.setattr(harness, "use_compile_cache", lambda *a: None)


def decide_inputs():
    cfg = harness.config_of(ROOT, "fleet-10k")
    cfg["jobs"] = 64
    traffic = harness.traffic_of(ROOT, "steady-table3")
    traffic["check_jobs"] = 64
    return cfg, traffic


def precopy_inputs(layers=2, block_elems=64):
    cfg = harness.config_of(ROOT, "replica-internlm2-1p8b")
    cfg["model"].update(num_layers=layers, d_model=128, num_heads=4,
                        num_kv_heads=2, d_ff=256, vocab_size=2048)
    cfg["serving"].update(batch=4, cache_len=256, prompt=16)
    # small blocks, so that one token dirties more than the stop threshold
    cfg["precopy"].update(block_elems=block_elems, max_rounds=4)
    return cfg, harness.traffic_of(ROOT, "decode")


def run(cell, cfg, traffic, seconds=0.5, control=False):
    return harness.run_cell(dict(cell), cfg, traffic, seed=SEED,
                            seconds=seconds, traced=False,
                            metrics=harness.cell_metrics(MAN, cell["name"],
                                                         False),
                            t_start=time.perf_counter(), control=control)


def test_decide_sound_run_is_correct():
    res = run(DECIDE, *decide_inputs())
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "tick_s", "tick_p95_s"}
    assert list(res)[-1] == "checks"


def test_precopy_sound_run_is_correct():
    res = run(PRECOPY, *precopy_inputs())
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "migration_s", "pause_s"}


@contextlib.contextmanager
def patched(obj, name, make):
    old = getattr(obj, name)
    setattr(obj, name, make(old))
    try:
        yield
    finally:
        setattr(obj, name, old)


def no_refit(old):
    def refresh(self, *a, **k):
        first = [j for j, job in self.jobs.items() if job.fitted_step < 0]
        return old(self, first) if first else 0
    return refresh


def half_refit(old):
    def refresh_group(self, jobs, latest, m, tail):
        h = max(1, len(jobs) // 2)
        return old(self, jobs[:h], latest[:h], m, tail)
    return refresh_group


def altered_answer(old):
    def postpone_rows(*a, **k):
        return old(*a, **k) + 1
    return postpone_rows


def decide_fault(kind):
    from repro.core import shard, surveillance
    eng = surveillance.SurveillanceEngine
    return {"state_unchanged": (eng, "refresh", no_refit),
            "half_batch": (eng, "_refresh_group", half_refit),
            "answer_altered": (shard, "postpone_rows", altered_answer)}[kind]


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "answer_altered"])
def test_decide_fault_is_not_correct(kind):
    obj, name, make = decide_fault(kind)
    with patched(obj, name, make):
        res = run(DECIDE, *decide_inputs())
    assert not res["correct"], res["checks"]


def merge_nothing(old):
    def merge(new, old_leaf, dirty, block):
        return old_leaf
    return merge


def scan_half(old):
    def dirty(new, old_leaf, block):
        m = old(new, old_leaf, block)
        return m.at[m.shape[0] // 2:].set(False)
    return dirty


def token_altered(old):
    def make(cfg, **kw):
        step = old(cfg, **kw)

        def serve_step(params, token, cache):
            nxt, logits, cache = step(params, token, cache)
            return (nxt + 1) % cfg.vocab_size, logits, cache
        return serve_step
    return make


def precopy_fault(kind):
    import repro.train
    from repro.core import precopy
    return {"state_unchanged": (precopy, "_leaf_merge", merge_nothing),
            "half_batch": (precopy, "_leaf_dirty", scan_half),
            "token_altered": (repro.train, "make_decode_step",
                              token_altered)}[kind]


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "token_altered"])
def test_precopy_fault_is_not_correct(kind):
    obj, name, make = precopy_fault(kind)
    with patched(obj, name, make):
        res = run(PRECOPY, *precopy_inputs())
    assert not res["correct"], res["checks"]


def test_decide_control_is_not_correct():
    """The reference computed in bfloat16, put in the program's place,
    fails a limit of the cell through the harness's own comparison."""
    res = run(DECIDE, *decide_inputs(), control=True)
    assert not res["correct"], res["checks"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_precopy_control_is_not_correct():
    """The float8 reference's tokens, in the program's place, read a gap
    over the cell's limit through the harness's own comparison. Eight
    layers, since float8's error grows with depth (0.40-0.78 over five
    seeds here; 0.65-0.97 at the cell's 24 layers on the chip), and one
    migration in the window, so that the tokens compared do not depend on
    the speed of the host."""
    res = run(PRECOPY, *precopy_inputs(layers=8, block_elems=256),
              seconds=0.0, control=True)
    assert not res["correct"], res["checks"]
    gap = res["checks"]["served_logit_gap"]
    assert gap["value"] > gap["limit"]
