"""The hybrid replica cell (``replica-granite-4h-micro.hybrid-decode``) on
the CPU, at a size a CPU holds: Mamba2 and attention layers in the order
M, M, A, M at hidden size 128, with the configuration's multipliers and no
position embedding.

- The serving program (prefill, then decode through the cache, by
  ``make_prefill_step`` and ``make_decode_step``) agrees with the plain
  reference's full forward, ``bench/ref/granite_hybrid.py``.
- The weights generator makes the program's own parameter tree, and at the
  configuration's full widths the state has the sizes the configuration
  states (shapes only, nothing allocated).
- Through ``harness.run_cell`` the cell's comparison reads ``correct`` true
  for a sound run and false for a broken one or for the control.
"""
import math
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import harness  # noqa: E402
from bench.drivers.precopy_hybrid import arch_of  # noqa: E402
from bench.gen import hybrid_weights as gen  # noqa: E402
from bench.ref import granite_hybrid as ref  # noqa: E402
from bench.tests.test_bench_correctness import (  # noqa: E402,F401
    cpu_device, patched, precopy_fault)

SEED = 3141592653
MAN = harness.manifest()
CELL = harness.find(MAN["workloads"], "replica-granite-4h-micro.hybrid-decode",
                    "workload")
FULL = harness.config_of(ROOT, "replica-granite-4h-micro")
#: float32 program against the float32 reference, logits of spread ~0.14:
#: only the order of float32 sums differs (2.4e-7 seen), while bfloat16
#: operands move them by ~5e-3 and a dropped multiplier by far more
F32_TOL = 1e-4
#: the served bfloat16 program: its weights' and activations' rounding
#: through four layers (4.6e-3 seen)
BF16_TOL = 2e-2


def small(dtype="float32", **over):
    c = dict(FULL, hidden_size=128, intermediate_size=256,
             num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=8,
             mamba_d_head=32, mamba_d_state=16, vocab_size=512,
             layer_types=["mamba", "mamba", "attention", "mamba"],
             num_hidden_layers=4, param_dtype=dtype)
    c.update(over)
    return c


def program_logits(c, tokens, prompt):
    """Prefill ``prompt`` positions, then decode the rest one token at a
    time through the cache: (B, S - prompt + 1, V) logits. The program is
    built from ``c``, the weights from ``small()`` in ``c``'s dtype."""
    from repro.train import make_decode_step, make_prefill_step
    arch = arch_of(c)
    params = gen.make_params(small(c["param_dtype"]), 7)
    logits, cache = jax.jit(make_prefill_step(arch, cache_len=32))(
        params, {"tokens": jnp.asarray(tokens[:, :prompt])})
    out = [logits]
    decode = jax.jit(make_decode_step(arch))
    for t in range(prompt, tokens.shape[1]):
        _, logits, cache = decode(params, jnp.asarray(tokens[:, t: t + 1]),
                                  cache)
        out.append(logits)
    return jnp.stack(out, 1).astype(jnp.float32)


def gap_to_reference(program_config, prompt=13):
    tokens = np.random.default_rng(0).integers(0, 512, (3, 21)).astype(
        np.int32)
    c = small()
    want = ref.logits(c, gen.make_params(c, 7), tokens, prompt - 1)
    got = program_logits(program_config, tokens, prompt)
    return float(jnp.max(jnp.abs(got - want)))


@pytest.mark.parametrize("prompt", [13, 16], ids=["ragged", "whole_chunks"])
def test_program_matches_reference(prompt):
    assert gap_to_reference(small(), prompt) < F32_TOL


def test_served_precision_matches_reference():
    assert gap_to_reference(small("bfloat16")) < BF16_TOL


@pytest.mark.parametrize("fault", [
    {"param_dtype": "bfloat16"}, {"embedding_multiplier": 1.0},
    {"residual_multiplier": 1.0}, {"attention_multiplier": 0.125},
    {"logits_scaling": 1.0}], ids=lambda f: next(iter(f)))
def test_tolerance_sees_a_fault(fault):
    """bfloat16 in place of float32, or one multiplier left out (the
    attention's left at 1/sqrt(head size)), fails the float32 tolerance."""
    assert gap_to_reference(small(**fault)) > F32_TOL


def test_weights_are_the_programs_tree():
    from repro.models import lm
    for c in (small(), FULL):
        arch = arch_of(c)
        want = jax.eval_shape(lambda: lm.init_params(arch, jax.random.key(0)))
        flat = jax.tree_util.tree_flatten_with_path(want)[0]
        spec = gen.tree_spec(c)
        assert list(spec) == ["/".join(k.key for k in p) for p, _ in flat]
        assert [(s, gen.dtype_of(p, c)) for p, s in spec.items()] == [
            (a.shape, a.dtype) for _, a in flat]


def test_full_width_state_sizes():
    """The served state at the configuration's widths, from shapes alone:
    1.70 B parameters (3.40 GB), a 0.54 GB KV buffer, a 0.60 GB float32 SSM
    state and a 7.5 MB conv window, 4.55 GB in all."""
    from repro.models import lm
    c, serve = FULL, FULL["serving"]
    arch = arch_of(c)
    params = gen.tree_spec(c)
    cache = jax.eval_shape(lambda: lm.init_cache(arch, serve["batch"],
                                                 serve["cache_len"]))
    nbytes = lambda t: sum(a.size * a.dtype.itemsize  # noqa: E731
                           for a in jax.tree.leaves(t))
    n_params = sum(math.prod(s) for s in params.values())
    assert 1.69e9 < n_params < 1.71e9
    layers = gen.kinds(c)
    B, W = serve["batch"], serve["cache_len"]
    kv = 2 * layers.count("attn") * B * W * c["num_key_value_heads"] * (
        c["hidden_size"] // c["num_attention_heads"]) * 2
    d_in, H, N, conv = gen.mamba_dims(c)
    ssm = layers.count("mamba_mlp") * B * H * N * c["mamba_d_head"] * 4
    window = layers.count("mamba_mlp") * B * (c["mamba_d_conv"] - 1) * conv * 2
    assert nbytes(cache["attn"]) == kv == 536870912
    # one stacked cache per run of layers: M x5, A, M x9, A, M x4
    assert [k.shape[0] for k in jax.tree.leaves(cache["attn"])] == [1] * 4
    conv_state, ssm_state = zip(*cache["mamba_mlp"])
    assert [(s.shape, s.dtype) for s in ssm_state] == [
        ((n, B, H, N, 64), jnp.float32) for n in (5, 9, 4)]
    assert nbytes(ssm_state) == ssm and nbytes(conv_state) == window
    total = nbytes(cache) + sum(math.prod(s) * gen.dtype_of(p, c).itemsize
                                for p, s in params.items())
    assert 4.50e9 < total < 4.60e9


def cell_inputs(layer_types=("mamba", "mamba", "attention", "mamba"),
                block_elems=64):
    c = small("bfloat16", vocab_size=2048, layer_types=list(layer_types),
              num_hidden_layers=len(layer_types))
    c["serving"] = dict(batch=4, cache_len=256, prompt=16, prefill_batch=2)
    c["precopy"] = dict(FULL["precopy"], block_elems=block_elems,
                        max_rounds=4)
    return c, harness.traffic_of(ROOT, CELL["traffic"])


def run(cfg, traffic, seconds=0.5, control=False):
    return harness.run_cell(dict(CELL), cfg, traffic, seed=SEED,
                            seconds=seconds, traced=False,
                            metrics=harness.cell_metrics(MAN, CELL["name"],
                                                         False),
                            t_start=time.perf_counter(), control=control)


def test_sound_run_is_correct():
    res = run(*cell_inputs())
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "migration_s", "pause_s"}


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "token_altered"])
def test_fault_is_not_correct(kind):
    obj, name, make = precopy_fault(kind)
    with patched(obj, name, make):
        res = run(*cell_inputs())
    assert not res["correct"], res["checks"]


def test_control_is_not_correct():
    """The float8 reference's tokens, in the program's place, read a gap
    over the cell's limit through the harness's own comparison (7.7e-3
    and 7.9e-3 at this size against 3.3e-4 and 6.4e-4 for the program).
    One migration in the window, so that the tokens compared do not depend
    on the speed of the host."""
    res = run(*cell_inputs(block_elems=256), seconds=0.0, control=True)
    assert not res["correct"], res["checks"]
    gap = res["checks"]["served_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_serve_step_roofline_reads_the_least_bytes_over_device_time():
    from bench import trace
    from bench.tests.test_bench_rooflines import PEAKS, fake_run, module
    work = module("serve_step_roofline").work
    assert work(3.4e9, 65536, 4097, 6.1e8) == 3.4e9 + 65536 * 4097 + 1.22e9
    args = {"weight_bytes": 3.4e9, "kv_position_bytes": 65536,
            "recurrent_bytes": 6.1e8}
    spans = [trace.Span("bench.decode", 0, 1, dict(args, positions=p))
             for p in (4097, 4099)]
    runs = [("jit_serve_step", 0.0, 1e7)] * 2                   # 10 ms each
    least = work(3.4e9, 65536, 4098, 6.1e8) / PEAKS["hbm_bytes_per_s"]
    read = harness.reader_of(ROOT, "serve_step_roofline")
    assert read(fake_run(spans, runs)) == pytest.approx(100 * least / 1e-2)
    # the dense cell's decode spans carry no bytes: nothing to read
    plain = [trace.Span("bench.decode", 0, 1, {})]
    assert read(fake_run(plain, runs)) is None


def test_counters_give_rounds_and_gigabytes_per_migration():
    run = harness.Run(None, {"rounds_per_migration": 15.0,
                             "sent_gb_per_migration": 13.76})
    assert harness.reader_of(ROOT, "rounds_per_migration")(run) == 15.0
    assert harness.reader_of(ROOT, "sent_gb_per_migration")(run) == 13.76
