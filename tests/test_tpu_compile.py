"""Compile the serving path's Pallas kernels for a described TPU v5e.

Nothing runs: each test lowers one kernel at qwen3-1.7b widths with
``interpret=False`` and compiles it with the TPU compiler (Mosaic) for a
``v5e:2x2`` topology that is described, not attached.  That catches what
interpret mode accepts and the chip refuses: blocks off the (8, 128)
tiling, lowerings Mosaic lacks, and tiles past the scoped VMEM limit.
Tiles are planned from the described device, so the v5e plan is what
compiles.  The topology is described inside a fixture (only the process
that runs these tests loads the TPU library); where it cannot be
described, the tests skip.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import planner
from repro.kernels.bi_transpose import bi_transpose
from repro.kernels.bp_scan import bp_scan
from repro.kernels.flash_attention import flash_attention
from repro.kernels.hbp_matmul import hbp_matmul

CFG = get_config("qwen3-1.7b")
SLOTS, CHUNK, MAX_LEN = 8, 256, 2048  # the chip smoke's engine geometry
H, KVH, HD = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim_
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """(sharding, planner device params) of one described v5e chip."""
    dev = topo.devices[0]
    return SingleDeviceSharding(dev), planner.device_params(dev)


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """A described-device compile is written to the persistent cache but
    cannot be read back without a chip; keep the cache out of these."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _attn_case(sq, sk, kv_dtype):
    """q/k/v shapes (heads folded into batch) for ``SLOTS`` rows."""
    return [((SLOTS * H, sq, HD), BF16), ((SLOTS * KVH, sk, HD), kv_dtype),
            ((SLOTS * KVH, sk, HD), kv_dtype)]


@pytest.mark.parametrize("case", ["prefill_first", "prefill_cont", "decode",
                                  "decode_int8"])
def test_flash_attention_compiles(chip, case):
    sharding, dp = chip
    sq, sk = (CHUNK, CHUNK) if case == "prefill_first" else (
        (CHUNK, MAX_LEN) if case == "prefill_cont" else (1, MAX_LEN))
    kv_dtype = jnp.int8 if case == "decode_int8" else BF16
    plan = planner.plan_attention(sq, sk, HD, BF16, dp, kv_dtype=kv_dtype)
    shapes = _attn_case(sq, sk, kv_dtype)
    if case == "prefill_first":
        def fn(q, k, v):
            return flash_attention(q, k, v, causal=True, n_heads=H,
                                   interpret=False, **plan)
    else:
        # traced per-row offsets and lengths, as the engine passes them
        shapes += [((SLOTS,), jnp.int32), ((SLOTS,), jnp.int32)]
        if case == "decode_int8":
            shapes += [((SLOTS * KVH,), jnp.float32)] * 2

        def fn(q, k, v, qoff, kvlen, *scales):
            sc = dict(zip(("k_scale", "v_scale"), scales))
            return flash_attention(q, k, v, causal=True, n_heads=H,
                                   q_offset=qoff, kv_len=kvlen,
                                   interpret=False, **plan, **sc)
    _compile(fn, *shapes, sharding=sharding)


def test_flash_attention_vjp_compiles(chip):
    sharding, dp = chip
    seq = 512
    plan = planner.plan_attention(seq, seq, HD, BF16, dp)

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, n_heads=H,
                              interpret=False, **plan)
        return jnp.sum(out.astype(jnp.float32))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)),
                    ((H, seq, HD), BF16), ((KVH, seq, HD), BF16),
                    ((KVH, seq, HD), BF16), sharding=sharding)
    assert text.count("tpu_custom_call") >= 3  # forward, dq, dk/dv


@pytest.mark.parametrize("m,k,n", [
    (SLOTS, CFG.d_model, CFG.d_ff),               # decode MLP up/gate
    (SLOTS * CHUNK, CFG.d_ff, CFG.d_model),       # prefill MLP down
    (998, CFG.d_model, CFG.q_dim),                # one-shot prefill, ragged
    (SLOTS, CFG.d_model, CFG.vocab_size),         # decode logits
])
def test_hbp_matmul_compiles(chip, m, k, n):
    sharding, dp = chip
    plan = planner.plan_matmul(m, k, n, BF16, dp)
    tiles = {t: plan[t] for t in ("bm", "bn", "bk")}
    _compile(lambda a, b: hbp_matmul(a, b, interpret=False, **tiles),
             ((m, k), BF16), ((k, n), BF16), sharding=sharding)


def test_bp_scan_compiles(chip):
    sharding, dp = chip
    shape = (SLOTS, 4096)
    block = planner.plan_scan(shape, jnp.float32, dp)["block"]
    _compile(lambda x: bp_scan(x, block=block, interpret=False),
             (shape, jnp.float32), sharding=sharding)


def test_bi_transpose_compiles(chip):
    sharding, dp = chip
    bt = planner.plan_transpose(CFG.d_model, CFG.d_ff, BF16, dp)["bt"]
    _compile(lambda x: bi_transpose(x, bt=bt, interpret=False),
             ((CFG.d_model, CFG.d_ff), BF16), sharding=sharding)


def test_kernels_compile_per_shard_on_2x2_mesh(topo):
    """On the (2, 2) mesh the launchers build over four chips, the model's
    kernel launches compile as per-shard programs (XLA refuses to partition
    a Mosaic kernel itself): a decode step's attention and projections."""
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core.sharding_hints import axis_rules, default_rules
    from repro.kernels import policy
    from repro.launch.mesh import make_mesh
    from repro.models import common

    mesh = make_mesh((2, 2), ("data", "model"),
                     devices=np.array(topo.devices).reshape(-1)[:4])
    dp = planner.device_params(topo.devices[0])
    rep = NamedSharding(mesh, P())

    def sds(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    def step(x, wq, wo, k, v, pos):
        q = common.project(x, wq).reshape(SLOTS, 1, H, HD)
        o = common.attention(q, k, v, pos[:, None],
                             jnp.arange(MAX_LEN, dtype=jnp.int32),
                             causal=True)
        return common.attn_out_project(o, wo)

    planner._DP_CACHE[(None, None)] = dp  # plan for the described chip
    try:
        with policy.apply(impl={"*": "pallas"}, interpret=False), mesh, \
                axis_rules(default_rules(mesh), mesh):
            args = (sds((SLOTS, 1, CFG.d_model)), sds((CFG.d_model, CFG.q_dim)),
                    sds((CFG.q_dim, CFG.d_model)),
                    sds((SLOTS, MAX_LEN, KVH, HD)),
                    sds((SLOTS, MAX_LEN, KVH, HD)), sds((SLOTS,), jnp.int32))
            text = jax.jit(step).lower(*args).compile().as_text()
    finally:
        planner.clear_device_params_cache()
    assert text.count("tpu_custom_call") >= 3
