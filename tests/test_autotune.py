"""Measured autotune layer: candidate envelope/divisibility invariants,
table JSON round-trips, replay semantics (cold cache = no-op, corrupt table
= ignored), dispatch integration, and the planner satellites (memoized
device_params, memory_stats query, dropped-override warning)."""
import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune, planner, registry


@pytest.fixture
def tune_dir(tmp_path, monkeypatch):
    """Redirect the tile table to a fresh directory and drop caches."""
    monkeypatch.setenv("REPRO_TUNE_DIR", str(tmp_path))
    autotune.clear_cache()
    yield tmp_path
    autotune.clear_cache()


def _sds_case(name, shapes, dtype):
    """ShapeDtypeStruct args — candidates/plans never need real buffers."""
    if name == "fft":
        dtype = jnp.complex64
    return tuple(jax.ShapeDtypeStruct(s, dtype) for s in shapes)


_CASES = {
    "scan": [(8, 8192)],
    "matmul": [(512, 384), (384, 768)],
    "transpose": [(512, 256)],
    "attention": [(4, 384, 64), (4, 384, 64), (4, 384, 64)],
    "fft": [(4, 1024)],
}


# -- candidate generation: the property the tuner must never break -----------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", sorted(_CASES))
def test_candidates_satisfy_divisibility_and_envelope(name, dtype):
    args = _sds_case(name, _CASES[name], jnp.dtype(dtype))
    dp = planner.DeviceParams("cpu", "test", 8 * 2**20, 64)
    info = autotune._TUNE[name]
    dims = info.dims(*args)
    cands = autotune.candidates(name, *args, dp=dp)
    assert cands, name
    assert cands[0] == dict(registry.get(name).plan(*args))  # analytic first
    seen = set()
    for plan in cands:
        key = tuple(sorted((k, str(v)) for k, v in plan.items()))
        assert key not in seen  # no duplicate timings
        seen.add(key)
        for k, v in plan.items():
            if k not in dims:  # variant knobs (backend/cutoff/morton)
                assert k in info.variant_keys, (name, k)
                continue
            assert dims[k] % v == 0, (name, plan)
        assert info.working_set(plan, *args) <= dp.fast_bytes, (name, plan)


def test_candidates_property_random_shapes():
    """Hypothesis sweep: every candidate for every op divides its axes and
    fits the queried fast memory, across random shapes/dtypes/memory sizes."""
    pytest.importorskip("hypothesis")  # optional dep: skip cleanly when absent
    from hypothesis import given, settings
    from hypothesis import strategies as st

    dims = st.integers(1, 10).map(lambda p: 2 ** p)
    odd_dims = st.integers(1, 1024)

    @given(name=st.sampled_from(sorted(autotune._TUNE)),
           a=dims, b=odd_dims, c=dims,
           dtype=st.sampled_from(["float32", "bfloat16", "int8"]),
           mem_pow=st.integers(16, 26))
    @settings(max_examples=60, deadline=None)
    def check(name, a, b, c, dtype, mem_pow):
        if name == "scan":
            shapes, dt = [(4, b)], jnp.dtype(dtype)
        elif name == "matmul":
            shapes, dt = [(a, b), (b, c)], jnp.dtype(dtype)
        elif name == "transpose":
            shapes, dt = [(a, b)], jnp.dtype(dtype)
        elif name == "attention":
            shapes, dt = [(2, a, 64)] * 3, jnp.dtype(dtype)
        else:  # fft: power-of-two length
            shapes, dt = [(2, a)], jnp.complex64
        args = tuple(jax.ShapeDtypeStruct(s, dt) for s in shapes)
        dp = planner.DeviceParams("cpu", "prop", 2 ** mem_pow, 64)
        info = autotune._TUNE[name]
        axis = info.dims(*args)
        for plan in autotune.candidates(name, *args, dp=dp):
            for k, v in plan.items():
                if k not in axis:  # variant knobs (backend/cutoff/morton)
                    assert k in info.variant_keys
                    continue
                assert axis[k] % v == 0
            assert info.working_set(plan, *args) <= dp.fast_bytes

    check()


# -- shape classes and snapping ----------------------------------------------

def test_shape_class_buckets_to_pow2():
    a = jax.ShapeDtypeStruct((384, 500), jnp.float32)
    b = jax.ShapeDtypeStruct((512, 512), jnp.float32)
    assert autotune.shape_class(a) == autotune.shape_class(b) == "512x512"
    assert autotune.entry_key("transpose", a).startswith("transpose|512x512|")


def test_entry_key_carries_semantic_flags():
    """Causal vs windowed vs decode attention must not share one table entry
    (same shape class, different measured optimum)."""
    q = jax.ShapeDtypeStruct((4, 512, 64), jnp.float32)
    kv = jax.ShapeDtypeStruct((4, 512, 64), jnp.float32)
    k_causal = autotune.entry_key("attention", q, kv, kv,
                                  kwargs={"causal": True, "window": 0})
    k_plain = autotune.entry_key("attention", q, kv, kv,
                                 kwargs={"causal": False, "window": 0})
    k_win = autotune.entry_key("attention", q, kv, kv,
                               kwargs={"causal": True, "window": 128})
    assert len({k_causal, k_plain, k_win}) == 3
    assert "causal=True" in k_causal and "window=128" in k_win
    # decode (sq != sk) is a derived flag: same kwargs, different key
    qd = jax.ShapeDtypeStruct((4, 1, 64), jnp.float32)
    k_dec = autotune.entry_key("attention", qd, kv, kv,
                               kwargs={"causal": True, "window": 0})
    assert "decode=True" in k_dec and "decode=False" in k_causal
    # omitted kwargs normalize to the kernel defaults: one key per config
    # regardless of calling convention
    assert autotune.entry_key("attention", q, kv, kv) == k_causal
    assert autotune.entry_key("attention", q, kv, kv,
                              kwargs={"causal": None}) == k_causal
    # flag-less ops keep the bare three-field key (no format churn)
    x = jax.ShapeDtypeStruct((4, 256), jnp.float32)
    assert autotune.entry_key("scan", x) == "scan|4x256|float32"


def test_snap_plan_restores_divisibility_across_class():
    # a plan recorded for n=512 replays on the same-class n=384 input
    x384 = jax.ShapeDtypeStruct((4, 384), jnp.float32)
    snapped = autotune.snap_plan("scan", (x384,), {"block": 512})
    assert 384 % snapped["block"] == 0 and snapped["block"] <= 512


# -- table persistence --------------------------------------------------------

def test_search_persists_and_roundtrips(tune_dir):
    x = jax.random.normal(jax.random.key(0), (2, 256))
    entry = autotune.search("scan", x, iters=2, max_candidates=4)
    # best-of includes the analytic point, so tuned can never measure worse
    assert entry["us"] <= entry["analytic_us"]
    path = autotune.table_path()
    assert path.exists()
    # round-trip through JSON: a cold process (cache cleared) sees the entry
    autotune.clear_cache()
    plan = autotune.lookup("scan", x)
    assert plan == entry["plan"]
    raw = json.loads(path.read_text())
    assert raw["version"] == autotune._TABLE_VERSION
    assert raw["jax_version"] == jax.__version__  # stamped on write
    assert len(raw["entries"]) == 1


def test_replay_cold_cache_is_noop(tune_dir):
    x = jax.random.normal(jax.random.key(0), (2, 256))
    with autotune.mode_scope("replay"):
        assert autotune.overlay("scan", (x,)) == {}
        got = registry.dispatch("scan", x, impl="pallas")
    want = registry.dispatch("scan", x, impl="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    assert not list(tune_dir.iterdir())  # replay never writes


@pytest.mark.parametrize("payload", [
    "not json at all {{{",
    '{"version": 99, "entries": {}}',
    '[1, 2, 3]',
    # pre-flag key format (table version 1): ignored wholesale, not migrated
    '{"version": 1, "entries": {"scan|4x256|float32": {"plan": {"block": "x"}}}}',
])
def test_corrupt_or_foreign_tables_are_ignored(tune_dir, payload):
    path = autotune.table_path()
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(payload)
    autotune.clear_cache()
    assert autotune.load_table() == {}  # never raises
    x = jax.random.normal(jax.random.key(0), (2, 256))
    with autotune.mode_scope("replay"):
        got = registry.dispatch("scan", x, impl="pallas")  # still runs
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray(registry.dispatch("scan", x, impl="ref")),
        rtol=1e-4, atol=1e-4)


def test_stale_jax_stamp_is_cold_cache(tune_dir):
    """A table tuned under another jaxlib replays nothing: tuned timings do
    not survive toolchain upgrades, so the stamp mismatch means cold."""
    x = jax.random.normal(jax.random.key(0), (2, 256))
    table = autotune.load_table()
    table[autotune.entry_key("scan", x)] = {"plan": {"block": 64}, "us": 1.0}
    path = autotune.save_table()
    raw = json.loads(path.read_text())
    raw["jax_version"] = "0.0.0-somebody-else"
    path.write_text(json.dumps(raw))
    autotune.clear_cache()
    assert autotune.load_table() == {}
    with autotune.mode_scope("replay"):
        assert autotune.overlay("scan", (x,)) == {}  # degrades, never replays


def test_dispatch_replays_tuned_plan(tune_dir):
    """A persisted (non-analytic) plan actually reaches the kernel, and
    explicit overrides still win over it."""
    x = jax.random.normal(jax.random.key(0), (2, 256))
    table = autotune.load_table()
    table[autotune.entry_key("scan", x)] = {"plan": {"block": 64}, "us": 1.0}
    autotune.save_table()
    with autotune.mode_scope("replay"):
        assert autotune.overlay("scan", (x,)) == {"block": 64}
        got = registry.dispatch("scan", x, impl="pallas")
        np.testing.assert_allclose(
            np.asarray(got),
            np.asarray(registry.dispatch("scan", x, impl="ref")),
            rtol=1e-4, atol=1e-4)
        # an explicit non-divisor override must still reach the kernel
        # (and trip its divisibility assert) — the tuned plan does not mask it
        with pytest.raises(AssertionError):
            registry.dispatch("scan", x, impl="pallas", block=60)


def test_search_mode_fills_table_from_dispatch(tune_dir):
    x = jax.random.normal(jax.random.key(0), (2, 128))
    with autotune.mode_scope("search"):
        registry.dispatch("scan", x, impl="pallas")
    assert autotune.lookup("scan", x) is not None  # miss triggered a search
    # under jit the args are tracers: search must degrade to replay, not time
    y = jax.random.normal(jax.random.key(1), (2, 64))
    with autotune.mode_scope("search"):
        jax.jit(lambda t: registry.dispatch("scan", t, impl="pallas"))(y)
    assert autotune.lookup("scan", y) is None


# -- mode knob ----------------------------------------------------------------

def test_mode_resolution(monkeypatch):
    # a launcher earlier in the test run may have pinned the process-wide
    # override (startup is documented to do so); isolate this test from it
    monkeypatch.setattr(autotune, "_mode_override", None)
    monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)
    assert autotune.mode() == "off"           # bare dispatch default
    assert autotune.resolve_mode() == "replay"  # launcher default
    assert autotune.resolve_mode("search") == "search"
    monkeypatch.setenv("REPRO_AUTOTUNE", "search")
    assert autotune.mode() == "search"
    assert autotune.resolve_mode() == "search"
    with pytest.raises(ValueError, match="unknown autotune mode"):
        autotune.resolve_mode("sideways")
    with pytest.raises(ValueError, match="unknown autotune mode"):
        autotune.set_mode("sideways")
    monkeypatch.setenv("REPRO_AUTOTUNE", "bogus")
    assert autotune.mode() == "off"  # bad env degrades, never raises


def test_run_options_resolution(monkeypatch):
    from repro.models.base import RunOptions

    monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)
    opts = planner.resolve_run_options(RunOptions())
    assert opts.autotune == "replay"
    assert planner.resolve_run_options(opts) is opts  # idempotent
    pinned = planner.resolve_run_options(RunOptions(autotune="off"))
    assert pinned.autotune == "off"
    monkeypatch.setenv("REPRO_AUTOTUNE", "search")
    assert planner.resolve_run_options(RunOptions()).autotune == "search"


# -- planner satellites -------------------------------------------------------

def test_device_params_memoized_with_clear_hook(monkeypatch):
    monkeypatch.delenv("REPRO_FAST_BYTES", raising=False)
    planner.clear_device_params_cache()
    dp1 = planner.device_params()
    assert planner.device_params() is dp1  # memoized object identity
    # REPRO_FAST_BYTES participates in the key: no stale hit after a flip
    monkeypatch.setenv("REPRO_FAST_BYTES", str(1 << 20))
    assert planner.device_params().fast_bytes == 1 << 20
    monkeypatch.delenv("REPRO_FAST_BYTES", raising=False)
    assert planner.device_params() is dp1
    planner.clear_device_params_cache()
    dp2 = planner.device_params()
    assert dp2 == dp1 and dp2 is not dp1  # hook really dropped the cache


class _FakeDev:
    platform = "cpu"
    device_kind = "fake-l2"

    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        if isinstance(self._stats, Exception):
            raise self._stats
        return self._stats


@pytest.mark.parametrize("stats,want", [
    ({"vmem_size_bytes": 4 * 2**20}, 4 * 2**20),          # explicit key wins
    ({"bytes_limit": 2 * 2**20}, 2 * 2**20),              # smaller than default
    ({"bytes_limit": 64 * 2**30}, 8 * 2**20),             # HBM-sized: ignored
    (None, 8 * 2**20),                                    # backend says nothing
    (RuntimeError("unimplemented"), 8 * 2**20),           # backend raises
])
def test_device_params_queries_memory_stats(monkeypatch, stats, want):
    monkeypatch.delenv("REPRO_FAST_BYTES", raising=False)
    dp = planner.device_params(_FakeDev(stats))
    assert dp.fast_bytes == want
    assert dp.kind == "fake-l2"


class _FakeTPU(_FakeDev):
    platform = "tpu"

    def __init__(self, kind):
        super().__init__({"bytes_limit": 16 * 2**30})  # HBM, not VMEM
        self.device_kind = kind


def test_device_params_tpu_reads_the_kind_table(monkeypatch):
    """A TPU's fast memory comes from the ``device_kind`` table (the
    scoped-VMEM limit), never from memory_stats()'s HBM-sized limit; a kind
    the table lacks is an error, not a default."""
    monkeypatch.delenv("REPRO_FAST_BYTES", raising=False)
    dp = planner.device_params(_FakeTPU("TPU v5 lite"))
    assert dp.fast_bytes == planner.TPU_VMEM_BYTES["TPU v5 lite"] == 16 * 2**20
    with pytest.raises(ValueError, match="TPU kind"):
        planner.device_params(_FakeTPU("TPU v99"))


@pytest.mark.parametrize("m", [998, 1021, 8])
def test_tpu_matmul_plan_takes_whole_axis_over_misaligned_tile(m):
    """On a TPU every block dim is a multiple of its tiling or the whole
    axis: a ragged row count (a prompt's token count) with no aligned
    divisor under the cap plans the whole axis, within fast memory."""
    dp = planner.DeviceParams("tpu", "TPU v5 lite", 16 * 2**20, 512)
    plan = planner.plan_matmul(m, 2048, 2048, jnp.bfloat16, dp)
    assert plan["bm"] == m or plan["bm"] % dp.sublane(jnp.bfloat16) == 0
    assert plan["bm"] == m  # 998 = 2*499, 1021 prime, 8 < one bf16 tile
    assert planner.matmul_working_set(plan["bm"], plan["bn"], plan["bk"],
                                      2) <= dp.fast_bytes


def test_plan_raises_when_no_legal_tile_fits():
    """A plan never quietly exceeds the fast memory it is given."""
    dp = planner.DeviceParams("tpu", "tiny", 64 * 2**10, 512)
    with pytest.raises(ValueError, match="fast memory"):
        planner.plan_matmul(998, 2048, 2048, jnp.bfloat16, dp)


def test_ref_path_warns_once_on_dropped_tile_overrides(monkeypatch):
    monkeypatch.setattr(registry, "_WARNED_DROPPED", set())
    x = jax.random.normal(jax.random.key(0), (2, 256))
    with pytest.warns(UserWarning, match="ignored on the ref path"):
        registry.dispatch("scan", x, impl="ref", block=64)
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # second call: warned once already
        registry.dispatch("scan", x, impl="ref", block=64)
        registry.dispatch("scan", x, impl="ref")  # no tiles: never warns
    monkeypatch.setenv("REPRO_STRICT_TILES", "1")
    with pytest.raises(ValueError, match="ignored on the ref path"):
        registry.dispatch("scan", x, impl="ref", block=64)
