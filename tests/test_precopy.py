"""Pre-copy live-migration engine invariants.

The central correctness property: after stop-and-copy the destination pytree
equals the source **exactly**, no matter how the job mutated state between
rounds. Plus the Xen stop conditions and the Strunk analytic bounds
(hypothesis)."""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, st

from repro.core import precopy, strunk


def _tree(rng, scale=1.0):
    return {
        "w1": jnp.asarray(rng.standard_normal((64, 128)) * scale, jnp.float32),
        "w2": jnp.asarray(rng.standard_normal((300,)) * scale, jnp.bfloat16),
        "step": jnp.asarray(7, jnp.int32),
    }


def test_migration_is_exact_with_live_updates():
    rng = np.random.default_rng(0)
    state = {"v": _tree(rng)}
    calls = {"n": 0}

    def step():
        calls["n"] += 1
        state["v"]["w1"] = state["v"]["w1"] + 0.01 * calls["n"]
        state["v"]["step"] = state["v"]["step"] + 1

    cfg = precopy.PrecopyConfig(block_elems=64, max_rounds=6,
                                stop_dirty_blocks=0)
    dest, report = precopy.migrate(lambda: state["v"], step, cfg)
    # exactness: destination == final source state bit-for-bit
    for a, b in zip(jax.tree.leaves(dest), jax.tree.leaves(state["v"])):
        assert jnp.array_equal(a, b), report
    assert calls["n"] >= 1                       # the job really ran
    assert report.outcome.rounds <= cfg.max_rounds
    assert report.outcome.bytes_sent >= report.v_mem


def test_idle_job_single_round():
    rng = np.random.default_rng(1)
    state = _tree(rng)
    cfg = precopy.PrecopyConfig(block_elems=128)
    dest, report = precopy.migrate(lambda: state, None, cfg)
    assert report.outcome.stop_reason == "dirty_low"
    assert report.outcome.bytes_sent == report.v_mem  # V_mem, no dirty resend
    # Strunk lower bound: T >= V/B
    lo, hi = strunk.strunk_bounds(report.v_mem, cfg.bandwidth)
    assert lo <= report.outcome.total_time <= hi


def test_total_cap_stop_condition():
    rng = np.random.default_rng(2)
    state = {"w": jnp.asarray(rng.standard_normal((4096,)), jnp.float32)}

    def hot_step():  # dirty everything every round
        state["w"] = state["w"] + 1.0

    cfg = precopy.PrecopyConfig(block_elems=64, max_rounds=29,
                                stop_dirty_blocks=0, stop_total_factor=3.0)
    dest, report = precopy.migrate(lambda: state["w"], hot_step, cfg)
    assert report.outcome.stop_reason in ("total_cap", "max_rounds")
    assert report.outcome.bytes_sent <= (3.0 + 2) * report.v_mem


@given(v_mem=st.floats(1e6, 1e10), bw=st.floats(1e7, 1e11),
       rate_frac=st.floats(0.0, 0.95))
def test_strunk_simulation_within_bounds(v_mem, bw, rate_frac):
    """Property: simulated pre-copy obeys Inequality 1 (both bounds)."""
    out = strunk.simulate_precopy(v_mem, bw, rate_frac * bw)
    lo, hi = strunk.strunk_bounds(v_mem, bw)
    assert lo <= out.total_time <= hi * 1.001
    assert 0 <= out.downtime <= out.total_time
    assert out.bytes_sent >= v_mem


@given(rate1=st.floats(0.0, 0.2), rate2=st.floats(0.5, 0.95))
def test_dirty_rate_monotonicity(rate1, rate2):
    """A dirtier workload never migrates cheaper — the paper's core premise."""
    v, bw = 1e9, 125e6
    a = strunk.simulate_precopy(v, bw, rate1 * bw)
    b = strunk.simulate_precopy(v, bw, rate2 * bw)
    assert a.bytes_sent <= b.bytes_sent
    assert a.total_time <= b.total_time * 1.001


def test_phase_dependent_migration_cost():
    """Migrating in an LM phase beats an NLM phase (Fig. 2 scenario)."""
    from repro.core.fleetsim import WorkloadTrace
    tr = WorkloadTrace([("MEM", 100), ("CPU", 100)], 200)
    in_mem = strunk.simulate_precopy(1e9, 125e6, tr.dirty_rate, start_time=10)
    in_cpu = strunk.simulate_precopy(1e9, 125e6, tr.dirty_rate, start_time=110)
    assert in_cpu.bytes_sent < in_mem.bytes_sent
    assert in_cpu.total_time < in_mem.total_time


# ---------------------------------------------------------------------------
# batched simulator: lane-for-lane bit-equality with the scalar reference
# ---------------------------------------------------------------------------
def _as_tuple(o: strunk.MigrationOutcome):
    return (o.total_time, o.downtime, o.bytes_sent, o.rounds, o.stop_reason)


def test_batch_bit_equals_reference_all_stop_reasons():
    """(M,) lanes covering all three Xen stop conditions, constant and
    callable (cyclic-trace) dirty rates, per-lane start times — every lane
    of the batch must equal the scalar reference EXACTLY (same float64
    operation order, not just approximately)."""
    from repro.core.fleetsim import WorkloadTrace
    tr = WorkloadTrace([("MEM", 100), ("CPU", 100)], 200)
    lanes = [
        (1.5e9, 125e6, 2e6, 0.0),            # dirty_low
        (1e9, 125e6, 150e6, 0.0),            # total_cap
        (1e9, 250e6, 0.55 * 250e6, 3.5),     # dirty_low after many rounds
        (2e9, 125e6, tr.dirty_rate, 10.0),   # NLM-phase start, trace rate
        (2e9, 125e6, tr.dirty_rate, 110.0),  # LM-phase start, trace rate
        (0.75e9, 100e6, 0.0, 42.0),          # idle lane, single round
    ]
    batch = strunk.simulate_precopy_batch(
        [l[0] for l in lanes], [l[1] for l in lanes],
        [l[2] for l in lanes], start_time=[l[3] for l in lanes])
    reasons = set()
    for i, (v, bw, rate, t0) in enumerate(lanes):
        ref = strunk.simulate_precopy_reference(v, bw, rate, start_time=t0)
        assert _as_tuple(batch.item(i)) == _as_tuple(ref), (i, ref)
        reasons.add(ref.stop_reason)
    assert {"dirty_low", "total_cap"} <= reasons


def test_batch_bit_equals_reference_max_rounds():
    # max_rounds needs a custom cap: at the Xen default the geometric dirty
    # tail either dips under the dirty_low threshold or trips total_cap first
    batch = strunk.simulate_precopy_batch(
        [1e9, 1e9], 125e6, [0.6 * 125e6, 2e6], max_rounds=5)
    for i, rate in enumerate((0.6 * 125e6, 2e6)):
        ref = strunk.simulate_precopy_reference(1e9, 125e6, rate,
                                                max_rounds=5)
        assert _as_tuple(batch.item(i)) == _as_tuple(ref)
    assert batch.item(0).stop_reason == "max_rounds"
    assert batch.item(1).stop_reason == "dirty_low"


def test_scalar_is_m1_view_of_batch():
    """simulate_precopy is the M=1 view of the batch path and matches the
    reference loop bit-for-bit."""
    from repro.core.fleetsim import WorkloadTrace
    tr = WorkloadTrace([("MEM", 30), ("CPU", 60), ("IDLE", 30)], 120)
    for t0 in (0.0, 17.0, 35.0, 95.0):
        a = strunk.simulate_precopy(1.2e9, 125e6, tr.dirty_rate,
                                    start_time=t0)
        b = strunk.simulate_precopy_reference(1.2e9, 125e6, tr.dirty_rate,
                                              start_time=t0)
        assert _as_tuple(a) == _as_tuple(b)


def test_batch_vectorized_rate_matches_per_lane_callables():
    """PiecewiseRate.batch (the fleet fast path) must sample identically to
    each lane's scalar callable."""
    from repro.core.fleetsim import PiecewiseRate, WorkloadTrace
    traces = [WorkloadTrace([("MEM", 100), ("CPU", 100)], 200, offset=o)
              for o in (0.0, 37.0, 121.0, 180.0)]
    v = np.full(4, 1.6e9)
    starts = np.array([0.0, 11.0, 63.0, 150.0])
    fast = strunk.simulate_precopy_batch(
        v, 125e6, PiecewiseRate.batch([t.rate_table for t in traces]),
        start_time=starts)
    slow = strunk.simulate_precopy_batch(
        v, 125e6, [t.dirty_rate for t in traces], start_time=starts)
    np.testing.assert_array_equal(fast.total_time, slow.total_time)
    np.testing.assert_array_equal(fast.bytes_sent, slow.bytes_sent)
    np.testing.assert_array_equal(fast.rounds, slow.rounds)
    np.testing.assert_array_equal(fast.stop_reason, slow.stop_reason)


def test_expected_cost_batch_matches_scalar_scan():
    from repro.core.fleetsim import WorkloadTrace
    tr = WorkloadTrace([("MEM", 50), ("CPU", 70)], 120)
    starts = np.linspace(0.0, 120.0, 13)
    batch = strunk.expected_cost_batch(1e9, 125e6, tr.dirty_rate, starts)
    scalar = [strunk.expected_cost(1e9, 125e6, tr.dirty_rate, start_time=s)
              for s in starts]
    np.testing.assert_array_equal(batch, scalar)


# ---------------------------------------------------------------------------
# the dirty scan reads each leaf in place: same masks as the block view
# ---------------------------------------------------------------------------
SCAN_BLOCK = 4096        # 32 lane chunks, as 16,384 is 128 on the replica

#: the replica's leaf kinds at small sizes, by how their rows meet a block
LEAF_SHAPES = {
    "weight_3d": (2, 64, 256),           # wq/wk/.. : 16 rows a block
    "weight_wide": (2, 32, 1024),        # w_gate/w_up: 4 rows a block
    "embed_vd": (301, 512),              # (V, d), tail-padded
    "head_dv": (16, 133 * 128),          # (d, V): blocks straddle rows,
                                         # over two lane tiles
    "head_dv_narrow": (64, 5 * 128),     # minor dim under one block
    "norm_2d": (2, 256),                 # one tail-padded block
    "final_norm_1d": (1152,),            # 1-D, tail-padded
    "kv_cache": (2, 2, 16, 8, 128),      # second-minor 8
}


def _block_view_mask(new, old, block):
    """The padded (n_blocks, block) view's max |new - old| > 0."""
    from repro.kernels import ref
    nb = -(-new.size // block)

    def view(x):
        return jnp.pad(x.reshape(-1), (0, nb * block - x.size)).reshape(
            nb, block)

    return np.asarray(ref.max_abs_delta_ref(view(new), view(old))[:, 0] > 0)


def _changed_at(rng, shape, dtype, where):
    size = int(np.prod(shape))
    flat = {"block_first": SCAN_BLOCK, "block_last": 2 * SCAN_BLOCK - 1,
            "before_tail": size - 1}[where] % size
    new = jnp.asarray(rng.standard_normal(shape), dtype)
    old = new.reshape(-1).at[flat].add(jnp.asarray(1, dtype)).reshape(shape)
    return new, old, flat


@pytest.mark.parametrize("where", ["block_first", "block_last",
                                   "before_tail"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("kind", list(LEAF_SHAPES))
def test_in_place_scan_masks_equal_block_view(kind, dtype, where):
    shape = LEAF_SHAPES[kind]
    new, old, flat = _changed_at(np.random.default_rng(3), shape, dtype,
                                 where)
    got = np.asarray(precopy._leaf_dirty(new, old, SCAN_BLOCK))
    want = _block_view_mask(new, old, SCAN_BLOCK)
    np.testing.assert_array_equal(got, want)
    assert np.flatnonzero(got).tolist() == [flat // SCAN_BLOCK]


def test_in_place_scan_int_scalar_stays_exact():
    pos = jnp.asarray(2 ** 24 + 1, jnp.int32)     # aliases 2**24 in f32
    got = precopy._leaf_dirty(pos, pos - 1, SCAN_BLOCK)
    assert np.asarray(got).tolist() == [True]


def _record_spans(monkeypatch) -> dict:
    """Span name -> its args and metadata, from ``precopy``'s spans, as if
    a profiler were recording."""
    args = {}

    class Recorder:
        def __init__(self, name, **kw):
            args.setdefault(name, {}).update(kw)
            self.name = name

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def set_metadata(self, **kw):
            args[self.name].update(kw)

    monkeypatch.setattr(precopy, "span", Recorder)
    monkeypatch.setattr(precopy, "enabled", lambda: True)
    return args


def _takes_view(leaf, block):
    """The shape rule: a float leaf whose minor dim and block are whole
    lane chunks, whose second-minor dim is a whole sublane tile (or whose
    (second-minor, minor) slabs tile a block), is read in place."""
    if not jnp.issubdtype(leaf.dtype, jnp.floating) or leaf.ndim == 0:
        return False
    if leaf.shape[-1] % 128 or block % 128:
        return False
    sublanes = max(8, 32 // leaf.dtype.itemsize)
    return (leaf.ndim <= 2 or leaf.shape[-2] % sublanes == 0
            or block % (leaf.shape[-2] * leaf.shape[-1]) == 0)


@pytest.mark.parametrize("block", [SCAN_BLOCK, 1 << 14, 64])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_scan_counts_the_leaves_read_in_place(monkeypatch, dtype, block):
    rng = np.random.default_rng(5)
    state = {k: jnp.asarray(rng.standard_normal(s), dtype)
             for k, s in LEAF_SHAPES.items()}
    state["pos"] = jnp.asarray(7, jnp.int32)
    state["odd"] = jnp.asarray(rng.standard_normal((4, 300)), dtype)
    state["kv_unaligned"] = jnp.asarray(rng.standard_normal((3, 8, 384)),
                                        dtype)
    args = _record_spans(monkeypatch)
    shadow = jax.tree.map(lambda x: x + 1, state)
    masks, n_dirty, _, _ = precopy.dirty_scan(state, shadow, block)
    leaves = jax.tree.leaves(state)
    want = sum(_takes_view(leaf, block) for leaf in leaves)
    assert args["precopy.scan"]["inplace"] == want
    assert args["precopy.scan"]["syncs"] == len(leaves)
    assert n_dirty == sum(-(-leaf.size // block) for leaf in leaves)
    if block == SCAN_BLOCK:
        assert want == len(leaves) - (3 if dtype == jnp.bfloat16 else 2)


def test_hybrid_replica_migrates_live_to_total_cap(monkeypatch):
    """A tiny Mamba2 / attention replica (layers M, M, A, M) decodes a token
    every round; each token rewrites every SSM state whole, so the bytes
    sent end the migration, and the destination is the source leaf by
    leaf."""
    from repro.configs import get_config
    from repro.models import lm
    from repro.train import make_decode_step, make_prefill_step

    M, A = "mamba_mlp", "attn"
    cfg = get_config("granite_4_0_h_micro").smoke().replace(
        num_layers=4, block_pattern=(M, M, A, M))
    params = lm.init_params(cfg, jax.random.key(0))
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (8, 12)), jnp.int32)
    _, cache = jax.jit(make_prefill_step(cfg, cache_len=64))(
        params, {"tokens": tokens})
    decode = jax.jit(make_decode_step(cfg))
    box = {"params": params, "cache": cache, "tok": tokens[:, -1:]}

    def step():
        box["tok"], _, box["cache"] = decode(box["params"], box["tok"],
                                             box["cache"])

    def state():
        return {"params": box["params"], "cache": box["cache"]}

    args = _record_spans(monkeypatch)
    cfg_pc = precopy.PrecopyConfig(block_elems=256, max_rounds=29,
                                   stop_dirty_blocks=0)
    dest, report = precopy.migrate(state, step, cfg_pc)
    for a, b in zip(jax.tree.leaves(dest), jax.tree.leaves(state())):
        assert jnp.array_equal(a, b)
    assert report.outcome.stop_reason == "total_cap"
    assert 1 < report.outcome.rounds < cfg_pc.max_rounds
    assert args["precopy.migrate"]["rounds"] == report.outcome.rounds
    assert args["precopy.stop_copy"] == {
        "stop": "total_cap", "sent_bytes": report.outcome.bytes_sent}


# ---------------------------------------------------------------------------
# the merge writes only the dirty boxes, in place: the same shadow as the
# padded block view's where
# ---------------------------------------------------------------------------
#: the cells' leaf kinds at small sizes: (shape, dtype, block, the dims
#: major to minor and the tile rows a v5e lays the cell's leaf out in)
MERGE_LEAVES = {
    "kv_bf16": ((2, 2, 64, 8, 128), jnp.bfloat16, 4096,
                ((0, 1, 2, 3, 4), 8)),
    "ssm_f32": ((2, 2, 4, 128, 64), jnp.float32, 1 << 14,
                ((0, 1, 2, 4, 3), 8)),
    "kv_head64": ((1, 4, 256, 8, 64), jnp.bfloat16, 1 << 14,
                  ((0, 1, 3, 4, 2), 8)),
    "in_proj_8512": ((1, 256, 8512), jnp.bfloat16, 1 << 14,
                     ((0, 2, 1), 8)),
    "vocab_rows": ((16, 133 * 128), jnp.bfloat16, 4096, ((0, 1), 8)),
    "conv_window": ((2, 16, 3, 4352), jnp.bfloat16, 1 << 14,
                    ((0, 2, 1, 3), 8)),
    "conv_ragged": ((2, 4, 3, 200), jnp.bfloat16, 4096,
                    ((0, 2, 1, 3), 8)),
    "norm_vector": ((2048,), jnp.bfloat16, 4096, ((0,), 8)),
    "int_scalar": ((), jnp.int32, 4096, ((), 8)),
}
MERGE_PATTERNS = ["none", "one", "last", "scattered", "all"]


def _padded_where(new, old, dirty, block):
    """The merge of the padded ``(n_blocks, block)`` view: a full-leaf
    ``where`` of the dirty blocks of ``new`` over ``old``."""
    nb = dirty.shape[0]

    def view(x):
        return jnp.pad(x.reshape(-1), (0, nb * block - x.size)).reshape(
            nb, block)

    out = jnp.where(dirty[:, None], view(new), view(old))
    return out.reshape(-1)[:new.size].reshape(old.shape)


def _dirty_pair(kind, pattern, seed=7):
    """(live leaf, shadow that differs from it in the dirty blocks only,
    mask, block)."""
    shape, dtype, block, _ = MERGE_LEAVES[kind]
    size = max(1, int(np.prod(shape)))
    nb = -(-size // block)
    dirty = np.zeros(nb, bool)
    if pattern == "one":
        dirty[nb // 2] = True
    elif pattern == "last":
        dirty[-1] = True
    elif pattern == "scattered":
        dirty[::3] = True
        dirty[min(1, nb - 1)] = True
    elif pattern == "all":
        dirty[:] = True
    rng = np.random.default_rng(seed)
    if jnp.issubdtype(dtype, jnp.floating):
        new = jnp.asarray(rng.standard_normal(shape), dtype)
    else:
        new = jnp.asarray(rng.integers(-2 ** 30, 2 ** 30, shape), dtype)
    hit = jnp.asarray(np.repeat(dirty, block)[:size].reshape(shape))
    old = jnp.where(hit, new + jnp.asarray(1, dtype), new)
    return new, old, jnp.asarray(dirty), block


@pytest.mark.parametrize("pattern", MERGE_PATTERNS)
@pytest.mark.parametrize("layout", ["row_major", "v5e"])
@pytest.mark.parametrize("kind", list(MERGE_LEAVES))
def test_merge_equals_the_padded_where(monkeypatch, kind, layout, pattern):
    """Bit for bit, in the layout the host gives (row-major) and in the
    one a v5e gives the cell's leaf, boxes and rounding included."""
    from repro.kernels import dirty_copy
    new, old, dirty, block = _dirty_pair(kind, pattern)
    want = np.asarray(_padded_where(new, old, dirty, block))
    merge = precopy._leaf_merge
    if layout == "v5e":
        order = MERGE_LEAVES[kind][3]
        monkeypatch.setattr(dirty_copy, "layout", lambda s, d: order)
        # a jit of its own: the module's caches the host's layout
        merge = jax.jit(precopy._leaf_merge.__wrapped__, static_argnums=3,
                        donate_argnums=1)
    got = merge(new, old, dirty, block)
    assert got.dtype == new.dtype and got.shape == new.shape
    np.testing.assert_array_equal(np.asarray(got), want)
    assert old.is_deleted()                  # the shadow was donated


WAITS_SCRIPT = textwrap.dedent("""
    import sys
    sys.path.insert(0, sys.argv[1])
    import numpy as np
    from jax.experimental.pallas import tpu as pltpu
    from repro.kernels import dirty_copy
    import test_precopy as t

    for kind in ("kv_bf16", "kv_head64", "ssm_f32", "in_proj_8512",
                 "vocab_rows"):
        order = t.MERGE_LEAVES[kind][3]
        dirty_copy.layout = lambda s, d, o=order: o
        for pattern in ("last", "scattered", "all"):
            new, old, dirty, block = t._dirty_pair(kind, pattern)
            want = np.asarray(t._padded_where(new, old, dirty, block))
            got = dirty_copy.copy_boxes(new, old, dirty, block,
                                        interpret=pltpu.InterpretParams())
            np.testing.assert_array_equal(np.asarray(got), want)
            p = dirty_copy.plan(new.shape, new.dtype, block)
            print("MERGED", kind, pattern, p.lanes, p.view[0], flush=True)
""")


def test_merge_kernel_waits_for_the_bytes_it_starts():
    """The TPU interpreter counts a DMA semaphore in bytes: every copy a
    run starts is waited for, class by class, with a wait of its own size
    (a wait of the wrong size blocks, so the script runs under a time
    limit; one too few leaves a count at the kernel's exit). Runs that end
    at the last unit, 128-lane columns (``lanes`` 2) and runs long enough
    for three copy sizes (1,064 units) are covered."""
    import os
    r = subprocess.run([sys.executable, "-c", WAITS_SCRIPT,
                        os.path.dirname(__file__)],
                       capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "non-zero count" not in r.stdout + r.stderr
    merged = [l.split()[1:] for l in r.stdout.splitlines()
              if l.startswith("MERGED")]
    assert len(merged) == 15
    assert {m[2] for m in merged} == {"1", "2"}            # lanes
    assert max(int(m[3]) for m in merged) >= 16 ** 2       # three classes


def test_merge_plans_copy_boxes_of_the_cells_leaves(monkeypatch):
    """In a v5e's layout every cell leaf kind but the tiny and ragged ones
    is copied box by box, and a dirty block's box is the block itself
    where the block is whole slabs or rows."""
    from repro.kernels import dirty_copy
    boxes = {}
    for kind, (shape, dtype, block, order) in MERGE_LEAVES.items():
        monkeypatch.setattr(dirty_copy, "layout", lambda s, d, o=order: o)
        p = dirty_copy.plan(shape, dtype, block)
        boxes[kind] = (p.view is not None, p.lanes, p.box_elems)
    assert boxes["kv_bf16"] == (True, 1, 4096)
    assert boxes["ssm_f32"] == (True, 1, 1 << 14)
    assert boxes["kv_head64"] == (True, 2, 64 * 8 * 128)   # a lane tile
    assert boxes["in_proj_8512"][:2] == (True, 2)
    assert not boxes["conv_ragged"][0] and not boxes["norm_vector"][0]
    assert not boxes["int_scalar"][0]


def test_migration_of_every_leaf_kind_is_exact():
    """A state of every kind, stepped between rounds, migrates to a
    destination equal to its source, leaf by leaf."""
    state = {k: _dirty_pair(k, "none")[0] for k in MERGE_LEAVES}
    calls = {"n": 0}

    def step():
        calls["n"] += 1
        for k in ("kv_bf16", "ssm_f32", "int_scalar"):
            x = state[k]
            state[k] = x.reshape(-1).at[calls["n"] * 997 % x.size].add(
                jnp.asarray(1, x.dtype)).reshape(x.shape)

    cfg = precopy.PrecopyConfig(block_elems=4096, max_rounds=4,
                                stop_dirty_blocks=0)
    dest, report = precopy.migrate(lambda: dict(state), step, cfg)
    assert calls["n"] == report.outcome.rounds
    for k in state:
        assert jnp.array_equal(dest[k], state[k]), k


def test_spread_merge_equals_the_padded_where():
    """The XLA path for a leaf spread over several devices writes the same
    shadow (run here on one device)."""
    for kind in ("kv_bf16", "vocab_rows", "int_scalar"):
        for pattern in ("one", "last", "scattered"):
            new, old, dirty, block = _dirty_pair(kind, pattern)
            want = np.asarray(_padded_where(new, old, dirty, block))
            got = precopy._leaf_merge_spread(new, old, dirty, block)
            np.testing.assert_array_equal(np.asarray(got), want)


SPREAD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    from repro.core import precopy

    def padded_where(new, old, dirty, block):
        nb = dirty.shape[0]
        view = lambda x: np.pad(x.reshape(-1), (0, nb * block - x.size)
                                ).reshape(nb, block)
        out = np.where(dirty[:, None], view(new), view(old))
        return out.reshape(-1)[:new.size].reshape(old.shape)

    rng = np.random.default_rng(3)
    for kind in (AxisType.Auto, AxisType.Explicit):
        mesh = jax.make_mesh((2,), ("x",), axis_types=(kind,))
        for shape, spec, block in (((4, 64, 128), P("x"), 4096),
                                   ((16, 133 * 128), P(None, "x"), 4096),
                                   ((6, 200), P("x"), 256)):
            nb = -(-int(np.prod(shape)) // block)
            dirty = np.zeros(nb, bool)
            dirty[::3] = dirty[-1] = True
            new = rng.standard_normal(shape).astype(np.float32)
            hit = np.repeat(dirty, block)[:new.size].reshape(shape)
            old = np.where(hit, new + 1, new)    # equal where clean
            want = padded_where(new, old, dirty, block)
            sharding = NamedSharding(mesh, spec)
            live = jax.device_put(new, sharding)
            # a buffer of the device's own (one put from numpy may alias
            # the host array, and then a donation copies)
            shadow = jax.device_put(old, sharding) * 1
            placed = shadow.sharding
            assert placed.is_equivalent_to(sharding, len(shape))
            buffers = [s.data.unsafe_buffer_pointer()
                       for s in shadow.addressable_shards]
            got, = precopy.merge_dirty([live], [shadow],
                                       [jnp.asarray(dirty)], block)
            np.testing.assert_array_equal(np.asarray(got), want)
            assert got.sharding == placed, (got.sharding, placed)
            assert shadow.is_deleted()
            assert [s.data.unsafe_buffer_pointer()
                    for s in got.addressable_shards] == buffers
    print("SPREAD_MERGE_OK")
""")


def test_spread_merge_keeps_the_sharding_and_buffers():
    """On two devices, under an Auto and an Explicit mesh, a leaf sharded
    along its leading or its minor dim merges to the padded ``where``'s
    values, keeps its sharding, and takes the donated shadow's buffers
    (a subprocess: the test process keeps one device)."""
    r = subprocess.run([sys.executable, "-c", SPREAD_SCRIPT],
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "SPREAD_MERGE_OK" in r.stdout


def _merge_state(seed=11):
    return {k: _dirty_pair(k, "scattered", seed)[:2]
            for k in ("kv_bf16", "vocab_rows", "conv_ragged", "norm_vector",
                      "int_scalar")}


def test_merge_span_carries_copied_bytes(monkeypatch):
    """Traced, the merge span's ``copied_bytes`` holds at least the bytes
    of the round's dirty blocks (within each leaf), and 0 on a round with
    nothing dirty; the count adds no host-device transfer."""
    pairs = _merge_state()
    live = {k: p[0] for k, p in pairs.items()}
    shadow = {k: p[1] for k, p in pairs.items()}
    args = _record_spans(monkeypatch)
    block = 4096
    masks, n_dirty, _, counts = precopy.dirty_scan(live, shadow, block)
    dirty_bytes = sum(min(d * block, max(1, leaf.size)) * leaf.dtype.itemsize
                      for d, leaf in zip(counts, jax.tree.leaves(live)))
    assert n_dirty > 0
    with jax.transfer_guard_device_to_host("disallow"):
        merged = precopy.merge_dirty(live, shadow, masks, block, counts)
    assert args["precopy.merge"]["copied_bytes"] >= dirty_bytes
    masks, n_dirty, _, counts = precopy.dirty_scan(live, merged, block)
    assert n_dirty == 0
    precopy.merge_dirty(live, merged, masks, block, counts)
    assert args["precopy.merge"]["copied_bytes"] == 0


def test_merge_span_untraced_sets_nothing(monkeypatch):
    """Without a profiler session the merge computes no count and sets no
    arg."""
    from repro.kernels import dirty_copy

    def counted(*a):
        raise AssertionError("counted without a profiler session")

    monkeypatch.setattr(dirty_copy, "copied_bytes", counted)
    pairs = _merge_state()
    live = {k: p[0] for k, p in pairs.items()}
    shadow = {k: p[1] for k, p in pairs.items()}
    masks, _, _, counts = precopy.dirty_scan(live, shadow, 4096)
    with jax.transfer_guard_device_to_host("disallow"):
        merged = precopy.merge_dirty(live, shadow, masks, 4096, counts)
    for k in live:
        assert jnp.array_equal(merged[k], live[k])
