"""Strassen-schedule matmul: kernel numerics vs the oracle across
dtypes/sizes (incl. shapes that must route classical), the planner's gate
on a Strassen request at the costmodel crossover (with a hypothesis
monotonicity property; unrequested, every plan is classical), v3 backend-flagged autotune keys (search/replay round-trip,
variant candidates, cross-shape interpolation), ragged hbp_matmul
overrides, and model-matmul routing parity (greedy decode + one train
step, impl="pallas" vs impl="jnp")."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import costmodel
from repro.kernels import autotune, planner, ref, registry
from repro.kernels.strassen_matmul import matmul as backend_matmul
from repro.kernels.strassen_matmul import strassen_matmul

DP = planner.DeviceParams(platform="cpu", kind="test", fast_bytes=8 * 2**20,
                          line_bytes=64)


@pytest.fixture
def tune_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_DIR", str(tmp_path))
    autotune.clear_cache()
    yield tmp_path
    autotune.clear_cache()


def _mats(n, dtype, seed=0):
    a = jax.random.normal(jax.random.key(seed), (n, n), jnp.float32)
    b = jax.random.normal(jax.random.key(seed + 1), (n, n), jnp.float32)
    return a.astype(dtype), b.astype(dtype)


def _tol(dtype):
    # Strassen's combination tree amplifies rounding: operands reach 2x
    # magnitude per level and the output combines cancel.  bf16 on N(0,1)
    # inputs at n<=512 stays within a few ulps of the ~sqrt(n) dot scale.
    if dtype == jnp.bfloat16:
        return dict(rtol=8e-2, atol=1.5)
    return dict(rtol=2e-3, atol=2e-3)


# -- kernel numerics ----------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,cutoff", [(128, 32), (256, 64), (192, 48)])
def test_strassen_matches_oracle(n, cutoff, dtype):
    """Multi-level recursion (incl. a non-pow2 even edge, 192 -> 96 -> 48)
    against the f32 oracle."""
    a, b = _mats(n, dtype, seed=n)
    out = strassen_matmul(a, b, cutoff=cutoff)
    assert out.dtype == dtype
    want = ref.matmul_ref(a, b)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_strassen_matches_textbook_recursion():
    """The signed ``_STRASSEN_LHS/RHS/OUT`` combination (index structure
    shared with the core simulator) is the same function as the textbook
    recursion in ``core.algorithms_jax``."""
    from repro.core.algorithms_jax import strassen as strassen_jnp

    a, b = _mats(128, jnp.float32)
    got = strassen_matmul(a, b, cutoff=32)
    want = strassen_jnp(a, b, leaf=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_strassen_ineligible_shape_falls_through():
    """Odd edges above the cutoff stop the recursion (big classical leaves),
    and a flat-out odd size falls straight to the tiled kernel / oracle."""
    for n in (130, 65):
        a, b = _mats(n, jnp.float32, seed=n)
        out = strassen_matmul(a, b, cutoff=32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref.matmul_ref(a, b)),
                                   rtol=2e-3, atol=2e-3)


def test_backend_matmul_dispatch_and_vjp():
    """The registry's matmul entry: explicit backend override, and
    gradients through both backends match the jnp grads."""
    a, b = _mats(128, jnp.float32)
    for backend in ("classical", "strassen"):
        got = registry.dispatch("matmul", a, b, impl="pallas",
                                backend=backend, cutoff=32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(a @ b),
                                   rtol=2e-3, atol=2e-3)
        da, db = jax.grad(
            lambda x, y: registry.dispatch(
                "matmul", x, y, impl="pallas", backend=backend,
                cutoff=32).sum(), argnums=(0, 1))(a, b)
        np.testing.assert_allclose(np.asarray(da), np.asarray(b.sum(1)[None, :] * jnp.ones_like(a)),
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(db), np.asarray(a.sum(0)[:, None] * jnp.ones_like(b)),
                                   rtol=2e-3, atol=2e-3)
    with pytest.raises(ValueError, match="unknown matmul backend"):
        backend_matmul(a, b, backend="winograd")


# -- planner backend selection ------------------------------------------------

def test_plan_matmul_backend_crossover():
    """A Strassen request recurses only above the modeled crossover, only
    for square pow2-friendly edges, only for fp32/bf16; the tile plan
    itself never picks Strassen."""
    cut = planner.strassen_cutoff(jnp.float32, DP)
    assert cut == costmodel.strassen_crossover_edge(
        DP.fast_bytes // 3 // 4, DP.line_bytes // 4)
    below = planner.plan_matmul_backend(cut, cut, cut, jnp.float32, DP)
    above = planner.plan_matmul_backend(2 * cut, 2 * cut, 2 * cut,
                                        jnp.float32, DP)
    assert below["backend"] == "classical" and "cutoff" not in below
    assert above["backend"] == "strassen" and above["cutoff"] == cut
    assert "backend" not in planner.plan_matmul(2 * cut, 2 * cut, 2 * cut,
                                                jnp.float32, DP)
    # non-square / low-precision / odd-above-cutoff shapes stay classical
    assert planner.plan_matmul_backend(2 * cut, cut, 2 * cut, jnp.float32,
                                       DP)["backend"] == "classical"
    assert planner.plan_matmul_backend(2 * cut, 2 * cut, 2 * cut, jnp.int8,
                                       DP)["backend"] == "classical"
    odd = 2 * (cut + 1)  # halves once to an odd edge just above the cutoff
    assert odd % 2 == 0 and (odd // 2) % 2 and odd // 2 > cut
    assert planner.plan_matmul_backend(odd, odd, odd, jnp.float32,
                                       DP)["backend"] == "classical"


def test_plan_matmul_backend_monotone_in_n():
    """Hypothesis property: over square power-of-two n, once the planner's
    gate admits a Strassen request it keeps admitting it for every larger n
    (at any queried fast-memory size and eligible dtype)."""
    pytest.importorskip("hypothesis")  # optional dep: skip cleanly when absent
    from hypothesis import given, settings
    from hypothesis import strategies as st

    @given(dtype=st.sampled_from(["float32", "bfloat16"]),
           mem_pow=st.integers(16, 28),
           line=st.sampled_from([64, 128, 512]))
    @settings(max_examples=40, deadline=None)
    def check(dtype, mem_pow, line):
        dp = planner.DeviceParams("cpu", "prop", 2 ** mem_pow, line)
        picks = [planner.plan_matmul_backend(n, n, n, dtype, dp)["backend"]
                 for n in (1 << j for j in range(5, 15))]
        first = picks.index("strassen") if "strassen" in picks else len(picks)
        assert all(p == "classical" for p in picks[:first])
        assert all(p == "strassen" for p in picks[first:])

    check()


# -- autotune: v3 keys, variants, interpolation -------------------------------

def test_entry_key_carries_matmul_backend_flag():
    a = jax.ShapeDtypeStruct((512, 512), jnp.float32)
    key = autotune.entry_key("matmul", a, a)
    assert "backend=classical" in key
    # a requested backend keys its own entry
    forced = autotune.entry_key("matmul", a, a, kwargs={"backend": "strassen"})
    assert "backend=strassen" in forced


def test_matmul_candidates_cover_backend_and_morton():
    a = jax.ShapeDtypeStruct((512, 512), jnp.float32)
    cands = autotune.candidates("matmul", a, a, dp=DP)
    assert cands[0] == dict(registry.get("matmul").plan(a, a))
    assert any(p.get("morton") is False for p in cands)
    # a classical request never tries the row-mixing Strassen schedule
    assert not any(p.get("backend") == "strassen" for p in cands)
    # a Strassen request walks its cutoff and offers classical
    asked = autotune.candidates("matmul", a, a, dp=DP,
                                variant={"backend": "strassen"})
    assert asked[0]["backend"] == "strassen"
    assert any(p.get("cutoff") == 256 for p in asked)
    assert any(p.get("backend") == "classical" for p in asked)
    # transpose tunes its morton flag too
    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    assert any(p.get("morton") is False
               for p in autotune.candidates("transpose", x, dp=DP))


def test_search_replay_roundtrip_with_backend_keys(tune_dir, monkeypatch):
    """Shrink the queried fast memory so a 256-edge matmul crosses into the
    Strassen regime, search it under a Strassen request, and replay the
    (backend-flagged) winner through dispatch."""
    monkeypatch.setenv("REPRO_FAST_BYTES", str(1 << 18))
    planner.clear_device_params_cache()
    try:
        plan = planner.plan_matmul_backend(256, 256, 256, jnp.float32)
        assert plan["backend"] == "strassen"
        a, b = _mats(256, jnp.float32)
        asked = {"backend": "strassen"}
        entry = autotune.search("matmul", a, b, iters=1, max_candidates=4,
                                **asked)
        assert entry["plan"].get("backend") in ("classical", "strassen")
        key = autotune.entry_key("matmul", a, b, kwargs=asked)
        assert "backend=strassen" in key
        autotune.clear_cache()  # force the JSON round-trip
        assert autotune.lookup("matmul", a, b, kwargs=asked) == entry["plan"]
        with autotune.mode_scope("replay"):
            got = registry.dispatch("matmul", a, b, impl="pallas", **asked)
        np.testing.assert_allclose(np.asarray(got), np.asarray(a @ b),
                                   rtol=2e-3, atol=2e-3)
    finally:
        planner.clear_device_params_cache()


def test_dispatch_keys_forced_variant_overrides(tune_dir, monkeypatch):
    """A call that pins ``backend=`` must key the overlay lookup on the
    forced variant, not the planner's own choice — otherwise a
    forced-classical run replays tiles tuned for the Strassen entry."""
    captured = {}
    orig = autotune.overlay

    def spy(op, args, *, search_kwargs=None):
        captured.update(search_kwargs or {})
        return orig(op, args, search_kwargs=search_kwargs)

    monkeypatch.setattr(autotune, "overlay", spy)
    a, b = _mats(64, jnp.float32)
    with autotune.mode_scope("replay"):
        registry.dispatch("matmul", a, b, impl="pallas", backend="classical")
    assert captured.get("backend") == "classical"


def test_overlay_interpolates_nearest_shape_class(tune_dir):
    """A table miss borrows the nearest recorded shape_class for the same
    (op, dtype, flags) instead of going cold; exact hits still win and
    foreign dtypes are never borrowed."""
    x512 = jax.random.normal(jax.random.key(0), (8, 512))
    x2048 = jax.random.normal(jax.random.key(1), (8, 2048))
    x1024 = jax.random.normal(jax.random.key(2), (8, 1024))
    table = autotune.load_table()
    table[autotune.entry_key("scan", x512)] = {"plan": {"block": 64}, "us": 1.0}
    table[autotune.entry_key("scan", x2048)] = {"plan": {"block": 512}, "us": 1.0}
    autotune.save_table()
    with autotune.mode_scope("replay"):
        # 1024 misses; 512 and 2048 are equidistant — deterministic pick,
        # snapped to the actual axis
        got = autotune.overlay("scan", (x1024,))
        assert got in ({"block": 64}, {"block": 512})
        # exact entry beats interpolation
        table = autotune.load_table()
        table[autotune.entry_key("scan", x1024)] = {"plan": {"block": 128},
                                                    "us": 1.0}
        autotune.save_table()
        assert autotune.overlay("scan", (x1024,)) == {"block": 128}
        # dtype mismatch: nothing to borrow
        xb = x1024.astype(jnp.bfloat16)
        assert autotune.overlay("scan", (xb,)) == {}


# -- ragged hbp_matmul overrides ----------------------------------------------

def test_hbp_matmul_ragged_override_snaps():
    """A non-divisor tile override snaps to the largest divisor instead of
    tripping the old ``m % bm == 0`` assert."""
    a, b = _mats(96, jnp.float32)
    got = registry.dispatch("matmul", a, b, impl="pallas",
                            bm=64, bn=64, bk=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(a @ b),
                               rtol=1e-3, atol=1e-3)


def test_hbp_matmul_degenerate_snap_falls_back():
    """Prime-ish dims whose best divisor is sub-sublane take the jnp oracle
    instead of a catastrophically fine grid."""
    a, b = _mats(31, jnp.float32)
    got = registry.dispatch("matmul", a, b, impl="pallas",
                            bm=16, bn=16, bk=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(a @ b),
                               rtol=1e-4, atol=1e-4)


# -- model routing parity -----------------------------------------------------

def _smoke_models():
    from repro.models import build_model
    from repro.models.base import RunOptions
    from repro.configs import get_smoke_config

    cfg = dataclasses.replace(get_smoke_config("qwen3-1.7b"), dtype="float32")
    mj = build_model(cfg, RunOptions(remat="none", matmul_impl="jnp"))
    mp = build_model(cfg, RunOptions(remat="none", matmul_impl="pallas"))
    return cfg, mj, mp


def test_model_matmul_impl_greedy_decode_parity():
    """Greedy decode tokens are identical with model matmuls routed through
    the kernel registry vs the jnp einsums (PR 3's end-to-end parity bar)."""
    cfg, mj, mp = _smoke_models()
    params = mj.init(jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(1), (2, 6), 3, cfg.vocab_size)
    max_len = 24

    def greedy(model, steps=4):
        logits, cache = jax.jit(
            lambda p, t: model.prefill(p, t, max_len))(params, {"tokens": prompt})
        dec = jax.jit(model.decode_step)
        cur = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        out = []
        for i in range(steps):
            out.append(np.asarray(cur[:, 0]))
            logits, cache = dec(params, cur, jnp.int32(6 + i), cache)
            cur = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        return np.stack(out)

    np.testing.assert_array_equal(greedy(mj), greedy(mp))


def test_model_matmul_impl_train_step_parity():
    """One train step (loss + grads) through the kernel route matches the
    jnp route — the matmul custom VJP under scan + chunked-xent remat."""
    cfg, mj, mp = _smoke_models()
    params = mj.init(jax.random.key(0))
    batch = {
        "tokens": jax.random.randint(jax.random.key(2), (2, 32), 3, cfg.vocab_size),
        "labels": jax.random.randint(jax.random.key(3), (2, 32), 0, cfg.vocab_size),
    }
    lj, gj = jax.value_and_grad(mj.loss)(params, batch)
    lp, gp = jax.value_and_grad(mp.loss)(params, batch)
    np.testing.assert_allclose(float(lj), float(lp), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(gj), jax.tree.leaves(gp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=1e-5)


def test_model_projection_is_batch_invariant(monkeypatch):
    """A model projection's row i depends on row i alone, whatever shares
    its batch, even for a square product where a Strassen request would
    recurse: the schedule is classical unless the policy asks, since
    Strassen adds row i to row i + m/2 in its quadrant sums.  (On the chip
    a prefill chunk of 8 slots x 256 tokens is exactly d_model = 2048
    rows.)  Asked for, Strassen does mix the rows."""
    from repro.kernels import policy
    from repro.models import common

    monkeypatch.setenv("REPRO_FAST_BYTES", str(1 << 18))  # Strassen above 128
    planner.clear_device_params_cache()
    assert planner.plan_matmul_backend(256, 256, 256,
                                       jnp.bfloat16)["backend"] == "strassen"
    x, w = _mats(256, jnp.bfloat16)
    other = x.at[128:].set(-x[128:])  # same first half, different second
    with policy.apply(impl={"matmul": "pallas"}):
        a = common.project(x, w)
        b = common.project(other, w)
    with policy.apply(impl={"matmul": "pallas"},
                      variants={"matmul": {"backend": "strassen"}}):
        sa = common.project(x, w)
        sb = common.project(other, w)
    planner.clear_device_params_cache()
    np.testing.assert_array_equal(np.asarray(a[:128]), np.asarray(b[:128]))
    assert not np.array_equal(np.asarray(sa[:128]), np.asarray(sb[:128]))
