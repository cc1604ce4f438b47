"""Each roofline's count of operations and bytes, on known shapes, and the
readers' arithmetic on a hand-made reduced trace."""
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, trace  # noqa: E402

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def reader(name):
    return harness.reader_of(ROOT, name)


def module(name):
    spec_path = ROOT / "bench" / "layers" / f"{name}.py"
    import importlib.util
    spec = importlib.util.spec_from_file_location(f"_t_{name}", spec_path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def fake_run(spans, modules, counters=None):
    red = trace.Reduced(window=(0.0, 1e9), busy={0: [(0.0, 5e8)]},
                        modules={0: modules}, spans=spans)
    return harness.Run(red, counters or {}, PEAKS)


def test_spectrum_work_is_an_fft_plus_reading_and_writing_once():
    flops, nbytes = module("spectrum_roofline").work(8, 512)
    assert flops == 8 * 2.5 * 512 * 9
    assert nbytes == 4 * 8 * (512 + 257)


def test_autocorr_work_is_one_dot_product_per_lag():
    flops, nbytes = module("autocorr_roofline").work(2, 10, 3, 5)
    assert flops == 2 * (2 * 7 + 2 * 6 + 2 * 5)
    assert nbytes == 4 * (2 * 10 + 3 + 2 * 3)


def test_dirty_scan_reads_live_and_shadow_once():
    assert module("dirty_scan_roofline").work(4.58e9) == 9.16e9


def test_spectrum_share_is_least_time_over_device_time():
    rows, n = 7500, 512
    span = trace.Span("bench.power_spectrum", 0, 1, {"rows": rows, "n": n})
    flops, nbytes = module("spectrum_roofline").work(rows, n)
    least = max(flops / PEAKS["bf16_flops_per_s"],
                nbytes / PEAKS["hbm_bytes_per_s"])
    run = fake_run([span], [("jit__dft_power", 0.0, 1e6)])   # 1 ms
    assert reader("spectrum_roofline")(run) == pytest.approx(
        100 * least / 1e-3)
    assert reader("spectrum_roofline")(fake_run([span], [])) is None


def test_autocorr_share_on_known_shapes():
    span = trace.Span("bench.autocorr_score", 0, 1,
                      {"rows": 7500, "n": 512, "lag_lo": 113, "lag_hi": 231})
    run = fake_run([span], [("jit__autocorr_score", 0.0, 2e6)])
    flops, nbytes = module("autocorr_roofline").work(7500, 512, 113, 231)
    least = max(flops / 197e12, nbytes / 819e9)
    assert reader("autocorr_roofline")(run) == pytest.approx(
        100 * least / 2e-3)


def test_scan_share_counts_scans_from_runs_per_leaf():
    span = trace.Span("bench.migrate", 0, 1,
                      {"state_bytes": 1e9, "leaves": 4})
    mods = [("jit__leaf_dirty", float(i), 1e6) for i in range(8)]  # 2 scans
    share = reader("dirty_scan_roofline")(fake_run([span], mods))
    assert share == pytest.approx(100 * (2 * 2e9 / 819e9) / 8e-3)


def test_per_round_and_per_step_times():
    span = trace.Span("bench.migrate", 0, 1, {"state_bytes": 1e9,
                                               "leaves": 4})
    mods = ([("jit__leaf_merge", 0.0, 2e6)] * 8
            + [("jit_serve_step", 0.0, 11e6)] * 3)
    run = fake_run([span], mods)
    assert reader("merge_ms_per_round")(run) == pytest.approx(8.0)
    assert reader("decode_ms_per_step")(run) == pytest.approx(11.0)


def test_idle_share_and_classify_time():
    run = fake_run([], [("jit__nb_predict_lm", 0.0, 3e6)] * 2, {"ticks": 4})
    assert reader("device_idle_share.decide")(run) == pytest.approx(50.0)
    assert reader("device_idle_share.precopy")(run) == pytest.approx(50.0)
    assert reader("classify_ms_per_tick")(run) == pytest.approx(1.5)
