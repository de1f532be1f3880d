"""Substrate: optimizer, schedules, compression, data pipeline, checkpoint,
fault tolerance, elastic resharding."""
import math
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dep: skip cleanly when absent
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.checkpoint import CheckpointManager, load_checkpoint, save_checkpoint
from repro.configs import get_smoke_config
from repro.data import DataConfig, SyntheticLMDataset
from repro.launch.mesh import make_mesh
from repro.optim import (
    AdamWConfig,
    adamw_init,
    adamw_update,
    linear_warmup_cosine,
)
from repro.optim.compression import compress_int8, decompress_int8, ef_compress
from repro.runtime import FaultTolerantRunner, StragglerMonitor


# -- optimizer ---------------------------------------------------------------

def test_adamw_first_step_matches_closed_form():
    cfg = AdamWConfig(lr=0.1, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.0,
                      grad_clip=0.0)
    params = {"w": jnp.ones((4,), jnp.float32)}
    grads = {"w": jnp.full((4,), 0.5, jnp.float32)}
    opt = adamw_init(params)
    new_params, new_opt, _ = adamw_update(params, grads, opt, cfg)
    # bias-corrected first step: mhat = g, vhat = g^2 -> delta = g/(|g|+eps) = 1
    np.testing.assert_allclose(np.asarray(new_params["w"]), 1.0 - 0.1, rtol=1e-5)
    assert int(new_opt["step"]) == 1


def test_adamw_grad_clip_applies():
    cfg = AdamWConfig(lr=0.1, grad_clip=1.0, weight_decay=0.0)
    params = {"w": jnp.zeros((1000,), jnp.float32)}
    grads = {"w": jnp.full((1000,), 10.0, jnp.float32)}
    opt = adamw_init(params)
    _, _, metrics = adamw_update(params, grads, opt, cfg)
    assert float(metrics["grad_norm"]) > 1.0  # reported pre-clip norm


def test_schedule_warmup_then_decay():
    fn = linear_warmup_cosine(1.0, warmup_steps=10, total_steps=110)
    assert float(fn(jnp.int32(0))) == 0.0
    assert float(fn(jnp.int32(10))) == pytest.approx(1.0, rel=1e-3)
    assert float(fn(jnp.int32(110))) < 0.2


# -- compression --------------------------------------------------------------

@given(st.integers(0, 10))
@settings(max_examples=10, deadline=None)
def test_int8_compression_bounded_error(seed):
    g = jax.random.normal(jax.random.key(seed), (256,), jnp.float32)
    q, s = compress_int8(g)
    back = decompress_int8(q, s)
    assert float(jnp.max(jnp.abs(back - g))) <= float(s) * 0.5 + 1e-6


def test_error_feedback_reduces_bias():
    """With EF, the accumulated compressed sum tracks the true sum."""
    rng = jax.random.split(jax.random.key(0), 50)
    grads = [jax.random.normal(k, (64,), jnp.float32) * 0.01 for k in rng]
    resid = {"g": jnp.zeros((64,), jnp.float32)}
    acc_c = jnp.zeros((64,))
    for g in grads:
        q, s, resid = ef_compress({"g": g}, resid)
        acc_c = acc_c + decompress_int8(q["g"], s["g"])
    acc_t = sum(grads)
    # residual carries the outstanding error: acc_c + resid == acc_t
    np.testing.assert_allclose(np.asarray(acc_c + resid["g"]), np.asarray(acc_t),
                               rtol=1e-3, atol=1e-4)


# -- data ----------------------------------------------------------------------

def test_data_determinism_and_packing():
    cfg = get_smoke_config("qwen3-1.7b")
    ds = SyntheticLMDataset(DataConfig(seed=3, global_batch=4, seq_len=64), cfg)
    b1, b2 = ds.batch_at(7), ds.batch_at(7)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert b1["tokens"].shape == (4, 64)
    assert (b1["tokens"] < cfg.vocab_size).all()
    # packing: no padding id inside (fully packed)
    assert (b1["tokens"] != 0).mean() > 0.95
    b3 = ds.batch_at(8)
    assert not np.array_equal(b1["tokens"], b3["tokens"])


def test_data_modality_extras():
    cfg = get_smoke_config("llama-3.2-vision-90b")
    ds = SyntheticLMDataset(DataConfig(global_batch=2, seq_len=32), cfg)
    b = ds.batch_at(0)
    assert b["image_embeds"].shape == (2, cfg.n_image_tokens, cfg.d_model)


# -- checkpoint ------------------------------------------------------------------

def _state():
    return {"params": {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3)},
            "opt_state": {"step": jnp.int32(5), "m": {"w": jnp.ones((2, 3))}}}


def test_checkpoint_roundtrip(tmp_path):
    state = _state()
    save_checkpoint(tmp_path, 5, state)
    step, loaded = load_checkpoint(tmp_path, state)
    assert step == 5
    np.testing.assert_array_equal(loaded["params"]["w"], state["params"]["w"])


def test_checkpoint_checksum_detects_corruption(tmp_path):
    state = _state()
    d = save_checkpoint(tmp_path, 1, state)
    victim = sorted(d.glob("leaf_*.npy"))[0]
    raw = bytearray(victim.read_bytes())
    raw[-1] ^= 0xFF
    victim.write_bytes(bytes(raw))
    with pytest.raises(IOError):
        load_checkpoint(tmp_path, state)


def test_checkpoint_retention_and_atomicity(tmp_path):
    state = _state()
    for s in [1, 2, 3, 4, 5]:
        save_checkpoint(tmp_path, s, state, keep=2)
    steps = sorted(int(p.name.split("_")[1]) for p in Path(tmp_path).glob("step_*"))
    assert steps == [4, 5]
    assert not list(Path(tmp_path).glob("*.tmp"))


def test_async_checkpoint_manager(tmp_path):
    mgr = CheckpointManager(tmp_path)
    mgr.save_async(7, _state())
    mgr.wait()
    step, loaded = mgr.restore_latest(_state())
    assert step == 7


# -- fault tolerance ----------------------------------------------------------------

def test_runner_retries_transient_failure(tmp_path):
    mgr = CheckpointManager(tmp_path)
    runner = FaultTolerantRunner(mgr, save_every=0, max_retries=2)
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("transient")
        return "ok"

    assert runner.run_step(0, flaky) == "ok"
    assert runner.retries == 2


def test_runner_gives_up_and_raises(tmp_path):
    mgr = CheckpointManager(tmp_path)
    runner = FaultTolerantRunner(mgr, save_every=0, max_retries=1)

    def always_fails():
        raise ValueError("hard")

    with pytest.raises(RuntimeError):
        runner.run_step(0, always_fails)


def test_straggler_monitor_flags_outliers():
    mon = StragglerMonitor(min_samples=10, k_sigma=3.0)
    for _ in range(20):
        assert not mon.observe(1.0 + np.random.default_rng(0).random() * 0.01)
    assert mon.observe(10.0)


def test_straggler_monitor_excludes_flagged_from_window():
    """Regression: a flagged sample must NOT enter the rolling window — one
    genuine straggler would otherwise inflate the std and mask the next
    (10.0 in a ~1.0 window pushes mean + 3*sigma past any moderate
    outlier)."""
    mon = StragglerMonitor(min_samples=10, k_sigma=3.0)
    rng = np.random.default_rng(0)
    for _ in range(20):
        mon.observe(1.0 + rng.random() * 0.01)
    assert mon.observe(10.0)
    assert 10.0 not in mon.times          # excluded from the stats window
    assert mon.observe(2.0)               # the next straggler still flags
    assert mon.flagged == 2


# -- elastic -----------------------------------------------------------------------

def test_elastic_reshard_roundtrip(tmp_path):
    """Save under one (trivial) mesh, restore under another plan."""
    import jax.sharding as shd

    from repro.runtime.elastic import replan_for_mesh

    state = _state()
    save_checkpoint(tmp_path, 2, state, mesh_shape={"data": 1, "model": 1})
    mesh = make_mesh((1, 1), ("data", "model"))
    plan = replan_for_mesh(
        {"params": state["params"],
         "opt_state": {"step": state["opt_state"]["step"],
                       "master": state["params"], "m": state["params"],
                       "v": state["params"]}},
        mesh,
    )
    assert isinstance(jax.tree.leaves(plan["params"])[0], shd.NamedSharding)
