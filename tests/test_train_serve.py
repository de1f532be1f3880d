"""Integration: end-to-end training (loss decreases; checkpoint/restart is
bit-deterministic), serving (prefill+decode loop), planner/dry-run machinery
on the real single-device backend."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPES, get_smoke_config
from repro.core import planner
from repro.data import DataConfig
from repro.launch.mesh import make_debug_mesh, make_mesh
from repro.launch.train import train
from repro.models.base import RunOptions


def small_mesh():
    return make_debug_mesh(1, tp=1)


@pytest.mark.slow
def test_training_loss_decreases(tmp_path):
    cfg = get_smoke_config("qwen3-1.7b")
    out = train(cfg, mesh=small_mesh(), steps=15,
                data_cfg=DataConfig(global_batch=4, seq_len=64),
                opts=RunOptions(remat="none"), log_every=0)
    first = np.mean(out["losses"][:3])
    last = np.mean(out["losses"][-3:])
    assert last < first, (first, last)


@pytest.mark.slow
def test_checkpoint_restart_is_deterministic(tmp_path):
    cfg = get_smoke_config("qwen3-1.7b")
    data_cfg = DataConfig(global_batch=2, seq_len=32, seed=5)
    kw = dict(mesh=small_mesh(), data_cfg=data_cfg, opts=RunOptions(remat="none"),
              log_every=0)

    # uninterrupted run
    full = train(cfg, steps=8, **kw)

    # interrupted: 4 steps + checkpoint, then resume to 8
    d = tmp_path / "ck"
    part1 = train(cfg, steps=4, ckpt_dir=str(d), save_every=4, **kw)
    part2 = train(cfg, steps=8, ckpt_dir=str(d), save_every=100, **kw)

    np.testing.assert_allclose(part2["losses"], full["losses"][4:], rtol=1e-5)


def test_serving_loop():
    from repro.launch.serve import Request, Server

    cfg = dataclasses.replace(get_smoke_config("qwen3-1.7b"), dtype="float32")
    server = Server(cfg, small_mesh(), max_len=64, opts=RunOptions(remat="none"))
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(3, cfg.vocab_size, 8).astype(np.int32), max_new=4)
            for i in range(2)]
    out = server.run_batch(reqs)
    assert out["tokens"] == 8
    assert all(len(r.out) == 4 for r in reqs)


@pytest.mark.slow
def test_greedy_decode_matches_teacher_forcing():
    """Serving correctness: tokens produced by the decode loop equal argmax
    of teacher-forced prefill logits at each step."""
    cfg = dataclasses.replace(get_smoke_config("qwen3-1.7b"), dtype="float32")
    from repro.models import build_model

    model = build_model(cfg, RunOptions(remat="none"))
    params = model.init(jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(1), (1, 6), 3, cfg.vocab_size)
    max_len = 32

    # decode loop
    logits, cache = model.prefill(params, {"tokens": prompt}, max_len)
    produced = []
    cur = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    for i in range(3):
        produced.append(int(cur[0, 0]))
        logits, cache = model.decode_step(params, cur, jnp.int32(6 + i), cache)
        cur = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)

    # teacher forcing with the produced tokens
    toks = jnp.concatenate([prompt, jnp.asarray([produced], jnp.int32)], axis=1)
    for i in range(3):
        lg, _ = model.prefill(params, {"tokens": toks[:, : 6 + i]}, max_len)
        assert int(jnp.argmax(lg, -1)[0]) == produced[i], i


# -- planner ---------------------------------------------------------------------

def test_planner_specs_divisible():
    """Every sharded dim must be divisible by its mesh axes (the balance
    condition as a hard planner invariant)."""
    import os

    from repro.launch.steps import abstract_params, build_step_bundle
    from repro.models import build_model

    cfg = get_smoke_config("qwen3-moe-30b-a3b")
    mesh = make_mesh((1, 1), ("data", "model"))
    model = build_model(cfg)
    ap = abstract_params(model)
    specs = planner.plan_params(ap, mesh)

    def check(leaf, spec):
        for dim, entry in zip(leaf.shape, spec):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            size = 1
            for a in axes:
                size *= mesh.shape[a]
            assert dim % size == 0

    jax.tree.map(check, ap, specs,
                 is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))


def test_hlo_analysis_counts_scan_flops():
    """The analyzer's raison d'être: flops inside lax.scan bodies are
    trip-count multiplied (cost_analysis undercounts them)."""
    from repro.launch.hlo_analysis import analyze

    def f(w, x):
        def body(h, _):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(body, x, None, length=6)
        return h.sum()

    w = jnp.ones((64, 64))
    x = jnp.ones((4, 64))
    txt = jax.jit(f).lower(w, x).compile().as_text()
    stats = analyze(txt)
    want = 2 * 4 * 64 * 64 * 6  # 6 iterations
    assert stats.flops >= want * 0.9, (stats.flops, want)


def test_shape_bytes_parsing():
    from repro.launch.hlo_analysis import shape_bytes

    assert shape_bytes("f32[2,3]{1,0}") == 24
    assert shape_bytes("bf16[4]") == 8
    assert shape_bytes("(f32[2], s32[3])") == 20
    assert shape_bytes("pred[]") == 1
