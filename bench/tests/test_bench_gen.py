"""The benchmark's copies of the load generators reproduce the program's
generators, so the copy was faithful when made."""
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench.gen import fleet as gen  # noqa: E402
from bench.ref import decide as ref  # noqa: E402

SEED = 3141592653


def test_table3_rows_match_fig10_make_fleet():
    from benchmarks import fig10_scalability as f10
    n, steps = 10, 24
    store, replay = f10._make_fleet(n, steps, seed=SEED, load="table3")
    mine = gen.make_load("table3", n, f10.WINDOW + steps, seed=SEED)
    window, _ = store.window_matrix(f10.WINDOW)
    np.testing.assert_array_equal(mine[:, :f10.WINDOW], window)
    np.testing.assert_array_equal(mine[:, f10.WINDOW:], replay)


@pytest.mark.parametrize("kind", ["heavy_tail", "correlated"])
def test_synthetic_loads_match(kind):
    from repro.data import synthetic
    program = {"heavy_tail": synthetic.heavy_tail_load,
               "correlated": synthetic.correlated_tenant_load}[kind]
    np.testing.assert_array_equal(gen.make_load(kind, 12, 40, seed=SEED),
                                  program(12, 40, seed=SEED))


def test_nb_training_set_matches_make_training_nb():
    from repro.core import characterize
    from repro.core.fleetsim import make_training_nb
    feats, labels = gen.nb_training_set(SEED, 500)
    want = make_training_nb(SEED, 500)
    got = characterize.fit(feats, labels)
    for a, b in ((got.bin_edges, want.bin_edges),
                 (got.log_likelihood, want.log_likelihood),
                 (got.log_prior, want.log_prior)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the reference fits the same tables from the same samples on its own
    mine = ref.fit_nb(feats, labels)
    np.testing.assert_array_equal(mine.edges, np.asarray(want.bin_edges))
    np.testing.assert_allclose(mine.loglik, np.asarray(want.log_likelihood),
                               rtol=1e-6)


def test_unknown_load_is_refused():
    with pytest.raises(ValueError):
        gen.make_load("diurnal", 2, 2, seed=0)
