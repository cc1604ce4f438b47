"""Checkpoint store: full/async/incremental roundtrips and restore-time
resharding hooks (elastic restart)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (AsyncCheckpointer, IncrementalCheckpointer,
                              latest_step, restore_checkpoint,
                              save_checkpoint)


def _state(seed=0, scale=1.0):
    rng = np.random.default_rng(seed)
    return {
        "params": {"w": jnp.asarray(rng.standard_normal((32, 48)) * scale,
                                    jnp.bfloat16),
                   "b": jnp.asarray(rng.standard_normal((48,)), jnp.float32)},
        "step": jnp.asarray(int(scale * 10), jnp.int32),
    }


def _assert_tree_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert jnp.array_equal(x, y)


def test_roundtrip(tmp_path):
    s = _state()
    save_checkpoint(str(tmp_path), 5, s)
    assert latest_step(str(tmp_path)) == 5
    like = jax.eval_shape(lambda: s)
    r = restore_checkpoint(str(tmp_path), 5, like)
    _assert_tree_equal(s, r)


def test_async_checkpointer_keeps_latest(tmp_path):
    ck = AsyncCheckpointer(str(tmp_path), keep=2)
    for step in (10, 20, 30):
        ck.save(step, _state(scale=step))
    ck.wait()
    steps = sorted(int(p.name.split("_")[1])
                   for p in tmp_path.glob("step_*"))
    assert steps == [20, 30]
    like = jax.eval_shape(lambda: _state())
    r = restore_checkpoint(str(tmp_path), 30, like)
    _assert_tree_equal(_state(scale=30), r)


def test_incremental_delta_then_restore(tmp_path):
    inc = IncrementalCheckpointer(str(tmp_path), block_elems=32,
                                  full_every=100)
    s = _state()
    stats0 = inc.save(0, s)
    assert stats0["kind"] == "full"
    # touch a single block's worth of params
    s2 = jax.tree.map(lambda x: x, s)
    s2["params"]["w"] = s["params"]["w"].at[0, 0].add(jnp.bfloat16(1.0))
    s2["step"] = s["step"] + 1
    stats1 = inc.save(1, s2)
    assert stats1["kind"] == "delta"
    full_bytes = sum(np.asarray(l).nbytes for l in jax.tree.leaves(s))
    assert stats1["bytes"] < full_bytes / 4        # delta is actually small
    like = jax.eval_shape(lambda: s)
    r = inc.restore(1, like)
    _assert_tree_equal(s2, r)
    r0 = inc.restore(0, like)
    _assert_tree_equal(s, r0)


@pytest.mark.parametrize("case", ["bf16_nan", "bf16_inf", "int64_high_word"])
def test_incremental_delta_is_exact(tmp_path, case):
    """A change beside a NaN or an unchanged inf in its block, and a change
    only above 2**32 in an int64 leaf, are saved and restored exactly."""
    w = np.arange(32 * 48, dtype=np.float32).reshape(32, 48)
    if case == "bf16_nan":
        w[0, 0] = np.nan                           # in the changed block
    if case == "bf16_inf":
        w[0, 0], w[0, 1] = np.inf, -np.inf
    s = {"w": np.asarray(w, jnp.bfloat16),
         "step": np.asarray([3, 2**40 + 7], np.int64)}
    s2 = {k: v.copy() for k, v in s.items()}
    if case == "int64_high_word":
        s2["step"][1] += 2**33
    else:
        s2["w"][0, 5] += 1
    inc = IncrementalCheckpointer(str(tmp_path), block_elems=32,
                                  full_every=100)
    inc.save(0, s)
    stats = inc.save(1, s2)
    assert stats["kind"] == "delta" and stats["bytes"] > 0
    with jax.enable_x64(True):                     # JAX holds int64 only so
        r = inc.restore(1, s)
    for k in s:
        assert np.array_equal(np.asarray(r[k]), s2[k], equal_nan=True), k


def test_trainer_restores_after_failure(tmp_path):
    """Integration: kill the job at a step, trainer resumes from checkpoint
    and reaches the target step with identical final state semantics."""
    from repro.configs import get_config
    from repro.runtime.trainer import Trainer, TrainerConfig

    cfg = get_config("internlm2_1p8b").smoke().replace(num_layers=2)
    failed = {"done": False}

    def failure_hook(step):
        if step == 7 and not failed["done"]:
            failed["done"] = True
            return True
        return False

    tcfg = TrainerConfig(ckpt_dir=str(tmp_path), ckpt_every=5,
                         telemetry=False)
    tr = Trainer(cfg, tcfg, batch=2, seq=32, failure_hook=failure_hook)
    out = tr.run(12)
    assert out["restarts"] == 1
    assert out["final_step"] == 12
    assert np.isfinite(out["loss"])
