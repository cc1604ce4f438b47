"""The program's trace: every host span goes through ``repro.spans`` under a
name listed in ``NAMES``, spans cost nothing without a profiler session,
and each program a trace is read by carries its device-side scope."""
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans
from repro.core import characterize, precopy
from repro.core import postpone as pp
from repro.kernels import autocorr, dft

SRC = pathlib.Path(spans.__file__).resolve().parent
SPAN_CALL = re.compile(r"\bspan\(\s*\"([^\"]+)\"")


def _sources():
    return [p for p in sorted(SRC.rglob("*.py")) if p.name != "spans.py"]


def test_names_are_unique():
    assert len(spans.NAMES) == len(set(spans.NAMES))


def test_every_span_in_the_program_is_listed():
    used = set()
    for p in _sources():
        text = p.read_text()
        used |= set(SPAN_CALL.findall(text))
        # one tracing system: spans and scopes only through repro.spans
        assert "TraceAnnotation" not in text, p
        assert "named_scope" not in text, p
    assert used == set(spans.NAMES)


def test_a_span_is_a_no_op_without_a_profiler():
    assert not spans.enabled()
    with spans.span("surveil.tick", jobs=3) as s:
        assert isinstance(s, jax.profiler.TraceAnnotation)


def _lowered(program):
    f32, i32 = jnp.float32, jnp.int32
    if program == "_nb_predict_lm":
        nb = characterize.fit(np.random.default_rng(0).random((64, 6)),
                              np.arange(64) % 4)
        return characterize._nb_predict_lm.lower(
            nb.bin_edges, nb.log_likelihood, nb.log_prior,
            jnp.zeros((4, 16, 6), f32), block=characterize.CLASSIFY_BLOCK)
    if program == "_dft_power":
        return dft._dft_power.lower(jnp.zeros((8, 128), f32), center=True,
                                    interpret=True)
    if program == "_autocorr_score":
        return autocorr._autocorr_score.lower(
            jnp.zeros((8, 128), f32), jnp.arange(4, dtype=i32),
            interpret=True)
    if program == "postpone_batch":
        return pp.postpone_batch_jit.lower(jnp.zeros((8, 16), jnp.int8),
                                           jnp.zeros(8, i32),
                                           jnp.zeros(8, i32))
    if program == "_leaf_dirty":
        x = jnp.zeros((64, 32), f32)
        return precopy._leaf_dirty.lower(x, x, 256)
    if program == "_leaf_merge":
        x = jnp.zeros((64, 32), f32)
        return precopy._leaf_merge.lower(x, x, jnp.zeros(8, bool), 256)
    raise KeyError(program)


@pytest.mark.parametrize("program,scope", [
    ("_nb_predict_lm", "classify"), ("_dft_power", "spectrum"),
    ("_autocorr_score", "autocorr"), ("postpone_batch", "postpone"),
    ("_leaf_dirty", "dirty_scan"), ("_leaf_merge", "merge")])
def test_each_traced_program_carries_its_scope(program, scope):
    text = _lowered(program).as_text(debug_info=True)
    assert f"{spans.PREFIX}{scope}" in text
