"""Bring-up smoke run of the decide plane and a live pre-copy on a TPU.

    python chip_smoke.py [--seed N]            # one chip
    python chip_smoke.py --chips 4 [--seed N]  # four chips: sharded decide

Phases, each printing its numbers on lines of its own:

  device   refuses to run unless JAX's default backend and the kernel
           dispatch row (``kernels.backend.kernel_backend``) are both the
           TPU; there is no CPU fallback.
  decide   a 10,000-job fleet on the paper's table-3 load (512-sample
           window, all ``DEFAULT_FIELDS``) through ``SurveillanceEngine``:
           one cold tick, 3 forced-refit ticks, 8 steady ticks. Then one
           more forced refit is checked job by job against the host
           reference engine (``use_kernel=False``, the numpy cycle fit):
           fitted period and Algorithm 2 RemainTime must agree except for
           printed near-ties (the two spectral bins, or the two candidate
           lags, within 1e-4 relative power). The spectrum and autocorr
           kernels are compiled at the shapes the tick used and must hold
           a ``tpu_custom_call``.
  precopy  one serving replica of ``internlm2_1p8b`` at full width (bf16
           params plus a batch-4 x 2,048-position KV cache) pre-copies
           with the default ``PrecopyConfig`` while decode keeps stepping
           between rounds; the destination must equal the source exactly.
  sharded  only with ``--chips 4``, and then the only phase besides the
           device check: a 100,000-job fleet through
           ``SurveillanceEngine(shards=4)`` and ``shards=None`` in this one
           process; the decide digests of every tick must be equal, and
           the sharded decide must span four devices.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``, printed only
when every phase passed; any failure raises and exits non-zero. JAX's
persistent compilation cache is ``$JAX_COMPILATION_CACHE_DIR`` when that is
set, else ``.jax_cache`` next to this file.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent

DECIDE_JOBS = 10_000
SHARDED_JOBS = 100_000
FORCED_TICKS = 3
STEADY_TICKS = 8
TIE_REL = 1e-4                  # near-tie: two candidates within this power
SERVE_CONFIG = "internlm2_1p8b"
SERVE_BATCH = 4
SERVE_CACHE_LEN = 2048
SERVE_PROMPT = 256
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class PhaseFailed(RuntimeError):
    pass


def require(ok: bool, msg: str) -> None:
    if not ok:
        raise PhaseFailed(msg)


def emit(phase: str, **kv) -> None:
    body = " ".join(f"{k}={json.dumps(v)}" for k, v in kv.items())
    print(f"{phase} {body}", flush=True)


class CompileCounter:
    """Records XLA backend compiles as (function name, seconds); programs
    loaded from the persistent cache are not compiles."""

    def __init__(self):
        import jax
        self.log: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    @property
    def n(self) -> int:
        return len(self.log)

    def _on_event(self, event: str, secs: float, fun_name: str = "?",
                  **_) -> None:
        if event == BACKEND_COMPILE:
            self.log.append((fun_name, secs))

    def slowest(self, start: int, k: int = 3) -> list:
        return sorted(self.log[start:], key=lambda e: -e[1])[:k]


def hbm_bytes(device, key: str = "peak_bytes_in_use") -> int:
    return int(device.memory_stats()[key])


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------
def device_phase(chips: int) -> dict:
    import jax
    backend = jax.default_backend()
    require(backend == "tpu", f"JAX's default backend is {backend!r}, "
            "not 'tpu'; this smoke run needs the chip")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.kernels import backend as kb
    require(kb.kernel_backend() == "tpu",
            f"kernel dispatch row is {kb.kernel_backend()!r}, not 'tpu'")
    devs = jax.devices()
    require(len(devs) >= chips,
            f"{chips} chips asked for but {len(devs)} are visible")
    cache = kb.use_compile_cache(ROOT / ".jax_cache")
    emit("device", platform=devs[0].platform, kind=devs[0].device_kind,
         count=len(devs), compile_cache=cache)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# decide tick
# ---------------------------------------------------------------------------
def _register(engine, nb, fleet, window):
    for i, view in enumerate(fleet.views()):
        engine.register(f"job{i:05d}", view, nb, window=window)
    return engine


def _spy_kernel_shapes(ops, seen: dict):
    """Wrap the two cycle-fit ops so the first call of each records the
    shapes the tick gave it. Returns a function that unwraps them."""
    originals = {"power_spectrum": ops.power_spectrum,
                 "autocorr_score": ops.autocorr_score}

    def wrap(name, fn):
        def spied(x, *args, **kwargs):
            seen.setdefault(name, (tuple(x.shape),)
                            + tuple(tuple(a.shape) for a in args))
            return fn(x, *args, **kwargs)
        return spied

    for name, fn in originals.items():
        setattr(ops, name, wrap(name, fn))

    def restore():
        for name, fn in originals.items():
            setattr(ops, name, fn)
    return restore


def _kernel_compiled(fn, *shapes) -> bool:
    import jax
    import jax.numpy as jnp
    args = [jax.ShapeDtypeStruct(s, jnp.float32 if i == 0 else jnp.int32)
            for i, s in enumerate(shapes)]
    return "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


def _kernel_parity(ops, X):
    """Max errors of the on-chip spectrum and autocorr kernels against the
    float64 numpy results, each relative to its row's largest value.
    X: (B, n) non-constant rows."""
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.autocorr import autocorr_score_ref
    Xc = X - X.mean(axis=1, keepdims=True)
    n = X.shape[1]
    P = np.asarray(ops.power_spectrum(X.astype(np.float32), center=True))
    F = np.fft.rfft(Xc, axis=1)
    P_ref = F.real ** 2 + F.imag ** 2
    spec = np.max(np.abs(P - P_ref) / P_ref.max(axis=1, keepdims=True))
    lags = np.arange(n // 2 + 1, dtype=np.int32)
    R = np.asarray(ops.autocorr_score(jnp.asarray(Xc, jnp.float32),
                                      jnp.asarray(lags)))
    R_ref = autocorr_score_ref(Xc, lags).astype(np.float64)
    scale = np.maximum(np.abs(R_ref).max(axis=1, keepdims=True), 1e-12)
    return float(spec), float(np.max(np.abs(R - R_ref) / scale))


def _explain(cycles, X, per_k, per_r, rem_k, rem_r):
    """Sort each disagreeing job into a near-tie or a hard disagreement.
    X: (B, n) the jobs' LM series. Returns a list of dicts."""
    import numpy as np
    n = X.shape[1]
    max_p = n // 2
    Xc = X - X.mean(axis=1, keepdims=True)
    P_r = cycles._spectra(X.astype(np.float32), use_kernel=False)
    P_k = cycles._spectra(X.astype(np.float32), use_kernel=True)
    tp = cycles._total_power(X)
    k_r = cycles._peak_pick(P_r, n, 2, max_p, total_power=tp)[0]
    k_k = cycles._peak_pick(P_k, n, 2, max_p, total_power=tp)[0]
    out = []
    for b in range(len(X)):
        row = {"period_kernel": int(per_k[b]), "period_ref": int(per_r[b]),
               "remain_kernel": int(rem_k[b]), "remain_ref": int(rem_r[b])}
        if per_k[b] == per_r[b]:
            row.update(kind="remain", rel=None, tie=False)
        elif k_k[b] != k_r[b]:
            a, c = P_r[b, k_r[b]], P_r[b, k_k[b]]
            rel = abs(a - c) / max(abs(a), 1e-30)
            row.update(kind="spectral_bin", bins=[int(k_r[b]), int(k_k[b])],
                       rel=float(rel), tie=bool(rel <= TIE_REL))
        else:
            x = Xc[b]
            ra = float(np.dot(x[: n - per_r[b]], x[per_r[b]:]))
            rc = float(np.dot(x[: n - per_k[b]], x[per_k[b]:]))
            rel = abs(ra - rc) / max(abs(ra), abs(rc), 1e-30)
            row.update(kind="autocorr_lag", rel=rel, tie=bool(rel <= TIE_REL))
        out.append(row)
    return out


def decide_phase(seed: int, counter: CompileCounter) -> None:
    import jax
    import numpy as np
    from benchmarks import fig10_scalability as f10
    from repro.core import cycles
    from repro.core.fleetsim import make_training_nb
    from repro.core.surveillance import SurveillanceEngine
    from repro.core.telemetry import DEFAULT_FIELDS
    from repro.kernels import ops

    W = f10.WINDOW
    t0 = time.perf_counter()
    nb = make_training_nb(seed)
    fleet, replay = f10._make_fleet(DECIDE_JOBS, FORCED_TICKS + STEADY_TICKS,
                                    seed=seed, load="table3")
    eng = _register(SurveillanceEngine(), nb, fleet, W)
    setup_s = time.perf_counter() - t0

    seen: dict = {}
    restore = _spy_kernel_shapes(ops, seen)
    c0 = counter.n
    try:
        t0 = time.perf_counter()
        res = eng.tick(W - 1)
        res.remain
        cold_s = time.perf_counter() - t0
    finally:
        restore()
    compiles = {"cold": counter.n - c0}
    slow = {"cold": counter.slowest(c0)}
    require(res.fleet == DECIDE_JOBS and res.refitted == DECIDE_JOBS,
            f"cold tick fitted {res.fleet} of {DECIDE_JOBS} jobs")

    step = W

    def timed(force: bool) -> float:
        nonlocal step, res
        fleet.record_fleet(step, replay[:, step - W])
        t = time.perf_counter()
        if force:
            eng.refresh(force=True)
        res = eng.tick(step)
        res.remain
        step += 1
        return time.perf_counter() - t

    c0 = counter.n
    forced = [timed(True) for _ in range(FORCED_TICKS)]
    compiles["forced"] = counter.n - c0
    slow["forced"] = counter.slowest(c0)
    c0 = counter.n
    steady = [timed(False) for _ in range(STEADY_TICKS)]
    compiles["steady"] = counter.n - c0
    slow["steady"] = counter.slowest(c0)
    emit("decide", n_jobs=DECIDE_JOBS, window=W, fields=len(DEFAULT_FIELDS),
         load="table3", seed=seed, setup_s=setup_s, cold_tick_s=cold_s,
         forced_refit_tick_s_median=statistics.median(forced),
         steady_tick_s_median=statistics.median(steady),
         forced_refit_tick_s=forced, steady_tick_s=steady,
         compiles=compiles, peak_hbm_bytes=hbm_bytes(jax.devices()[0]))
    emit("decide_compiles_slowest", **slow)

    # kernel path: the Pallas kernels, not their fallbacks, at tick shapes
    require(set(seen) == {"power_spectrum", "autocorr_score"},
            f"the tick called only {sorted(seen)} of the cycle-fit kernels")
    spec_k = _kernel_compiled(lambda x: ops.power_spectrum(x, center=True),
                              *seen["power_spectrum"])
    ac_k = _kernel_compiled(ops.autocorr_score, *seen["autocorr_score"])
    varied = [job.lm_series for job in eng.jobs.values()
              if job.lm_series.min() != job.lm_series.max()]
    require(len(varied) > 0, "no job has a varying LM series")
    spec_err, ac_err = _kernel_parity(
        ops, np.stack(varied[:256]).astype(np.float64))
    emit("decide_kernels", power_spectrum_shape=seen["power_spectrum"],
         power_spectrum_tpu_custom_call=spec_k,
         autocorr_score_shapes=seen["autocorr_score"],
         autocorr_score_tpu_custom_call=ac_k,
         spectrum_max_rel_err=spec_err, autocorr_max_rel_err=ac_err)
    require(spec_k and ac_k, "a cycle-fit op compiled without its kernel")

    # correctness: one more forced refit against the host reference engine
    last = step - 1
    eng.refresh(force=True)
    rk = eng.tick(last).remain
    ref = _register(SurveillanceEngine(use_kernel=False), nb, fleet, W)
    rr = ref.tick(last).remain
    ids = sorted(eng.jobs)
    require(sorted(rk) == sorted(rr) == ids, "fitted job sets differ")
    lm_diff = sum(not np.array_equal(eng.jobs[j].lm_series,
                                     ref.jobs[j].lm_series) for j in ids)
    per_k = np.array([eng.jobs[j].model.period for j in ids])
    per_r = np.array([ref.jobs[j].model.period for j in ids])
    rem_k = np.array([rk[j] for j in ids])
    rem_r = np.array([rr[j] for j in ids])
    bad = np.flatnonzero((per_k != per_r) | (rem_k != rem_r))
    rows = []
    if bad.size:
        X = np.stack([ref.jobs[ids[b]].lm_series for b in bad]
                     ).astype(np.float64)
        rows = _explain(cycles, X, per_k[bad], per_r[bad], rem_k[bad],
                        rem_r[bad])
    ties = [dict(job=ids[b], **r) for b, r in zip(bad, rows) if r["tie"]]
    hard = [dict(job=ids[b], **r) for b, r in zip(bad, rows)
            if not r["tie"]]
    for row in ties:
        emit("decide_near_tie", **row)
    for row in hard[:20]:
        emit("decide_disagreement", **row)
    emit("decide_check", step=last, jobs=len(ids),
         period_disagree=int((per_k != per_r).sum()),
         remain_disagree=int((rem_k != rem_r).sum()),
         lm_series_disagree=lm_diff, near_ties=len(ties), hard=len(hard))
    require(lm_diff == 0, f"{lm_diff} LM series differ from the reference")
    require(not hard, f"{len(hard)} jobs disagree with the host reference "
            "beyond near-ties")


# ---------------------------------------------------------------------------
# live pre-copy of a serving replica
# ---------------------------------------------------------------------------
def precopy_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core import precopy
    from repro.data import make_batch
    from repro.models import lm
    from repro.train import make_decode_step, make_prefill_step

    dev = jax.devices()[0]
    cfg = get_config(SERVE_CONFIG)
    t0 = time.perf_counter()
    params = lm.init_params(cfg, jax.random.key(seed))
    batch = make_batch(cfg, SERVE_BATCH, SERVE_PROMPT, seed=seed)
    batch.pop("targets")
    prefill = jax.jit(make_prefill_step(cfg, cache_len=SERVE_CACHE_LEN))
    decode = jax.jit(make_decode_step(cfg))
    logits, cache = prefill(params, batch)
    box = {"tok": jnp.argmax(logits, -1)[:, None].astype(jnp.int32),
           "cache": cache, "decoded": 0}

    def decode_once():
        box["tok"], _, box["cache"] = decode(params, box["tok"], box["cache"])
        box["decoded"] += 1

    decode_once()                     # compile decode before the copy
    jax.block_until_ready(box["cache"])
    box["decoded"] = 0
    setup_s = time.perf_counter() - t0

    def state():
        return {"params": params, "cache": box["cache"]}

    param_bytes = precopy.total_bytes(params)
    cache_bytes = precopy.total_bytes(box["cache"])
    cfg_pc = precopy.PrecopyConfig()
    t0 = time.perf_counter()
    dest, report = precopy.migrate(state, decode_once, cfg_pc)
    wall_s = time.perf_counter() - t0
    src = state()
    exact = all(bool(jnp.array_equal(a, b)) for a, b in
                zip(jax.tree.leaves(dest), jax.tree.leaves(src)))
    out = report.outcome
    emit("precopy", config=SERVE_CONFIG, param_dtype=cfg.param_dtype,
         batch=SERVE_BATCH, cache_len=SERVE_CACHE_LEN, prompt=SERVE_PROMPT,
         cut="none", param_bytes=param_bytes, cache_bytes=cache_bytes,
         setup_s=setup_s, block_elems=cfg_pc.block_elems,
         rounds=out.rounds, stop_reason=out.stop_reason,
         bytes_sent=out.bytes_sent, wall_s=wall_s,
         engine_wall_s=report.wall_time,
         modelled_downtime_s=out.downtime, modelled_total_s=out.total_time,
         modelled_bandwidth=cfg_pc.bandwidth,
         tokens_decoded_during_copy=box["decoded"],
         dest_exact=exact, peak_hbm_bytes=hbm_bytes(dev))
    require(exact, "the migrated replica differs from the source")
    tok, _, _ = decode(dest["params"], box["tok"], dest["cache"])
    require(tok.shape == box["tok"].shape, "decode did not resume on the "
            "destination")


# ---------------------------------------------------------------------------
# sharded decide (four chips)
# ---------------------------------------------------------------------------
def sharded_phase(seed: int, chips: int) -> None:
    import jax
    import numpy as np
    from benchmarks import fig10_scalability as f10
    from repro.core import shard as shardlib
    from repro.core.fleetsim import make_training_nb

    W = f10.WINDOW
    t0 = time.perf_counter()
    nb = make_training_nb(seed)
    fleet, replay = f10._make_fleet(SHARDED_JOBS, 2, seed=seed,
                                    load="table3")
    sharded = f10._make_engine(nb, fleet, shards=chips)
    single = f10._make_engine(nb, fleet)
    setup_s = time.perf_counter() - t0
    mesh_ids = sorted(d.id for d in sharded.mesh.devices.flat)
    require(len(set(mesh_ids)) == chips,
            f"the decide mesh holds devices {mesh_ids}, not {chips}")
    emit("sharded_setup", n_jobs=SHARDED_JOBS, shards=chips,
         mesh_device_ids=mesh_ids, setup_s=setup_s)

    step = W - 1
    for stage in ("cold", "forced_refit", "steady"):
        if stage != "cold":
            fleet.record_fleet(step, replay[:, step - W])
        times, digests = {}, {}
        for name, eng in (("shards4", sharded), ("single", single)):
            t = time.perf_counter()
            if stage == "forced_refit":
                eng.refresh(force=True)
            res = eng.tick(step)
            digests[name] = f10._remain_digest(res)
            times[name] = time.perf_counter() - t
        emit("sharded_tick", stage=stage, step=step,
             tick_s_shards4=times["shards4"], tick_s_single=times["single"],
             digest_shards4=digests["shards4"],
             digest_single=digests["single"], fleet=res.fleet)
        require(digests["shards4"] == digests["single"],
                f"{stage} tick: sharded digest differs from single-device")
        step += 1

    ids, origins, profiles, periods, _ = sharded._packed_fleet()
    m_now = (step - 1 - origins).astype(np.int32)
    out = shardlib.postpone_rows(profiles, periods, m_now, sharded.mesh)
    placed = sorted(d.id for d in out.sharding.device_set)
    in_use = [hbm_bytes(d, "bytes_in_use") for d in jax.devices()[:chips]]
    emit("sharded_placement", decide_output_device_ids=placed,
         bytes_in_use_per_device=in_use,
         peak_hbm_bytes_per_device=[hbm_bytes(d)
                                    for d in jax.devices()[:chips]])
    require(placed == mesh_ids, f"the sharded decide ran on {placed}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the fleet, classifier and model weights")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded decide across four chips")
    args = ap.parse_args(argv)
    device = device_phase(args.chips)
    counter = CompileCounter()
    if args.chips == 4:
        sharded_phase(args.seed, args.chips)
    else:
        decide_phase(args.seed, counter)
        precopy_phase(args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
