"""Ambient ExecutionPolicy: the one dispatch-resolution API.

Covers the policy value object (wildcard precedence, functional update),
the context stack (nesting, restore-on-exit, exception unwind, thread and
jit-trace safety), environment assembly (``REPRO_IMPL`` grammar,
``REPRO_STRICT_TILES``, ``REPRO_INTERPRET``), the generic resolver's
capability gates, variant overrides flowing into dispatch, the RunOptions
compat shim (identical greedy-decode tokens and train-step loss/grads vs
the equivalent explicit policy, dense + hybrid), the scoped ring-buffer
pin, the warn-once reset hook, and the shared kernel/simulator namespace.
"""
import contextlib
import dataclasses
import threading
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.kernels import autotune, policy, registry
from repro.models import build_model
from repro.models.base import RunOptions


# -- the value object ---------------------------------------------------------

def test_wildcard_precedence():
    pol = policy.ExecutionPolicy(impl={"attention": "pallas", "*": "jnp"})
    assert pol.impl_for("attention") == "pallas"  # own entry beats wildcard
    assert pol.impl_for("matmul") == "jnp"        # wildcard covers the rest
    assert policy.ExecutionPolicy().impl_for("matmul") == "auto"  # default


def test_policy_is_frozen_and_validated():
    pol = policy.ExecutionPolicy(impl={"*": "pallas"})
    with pytest.raises(dataclasses.FrozenInstanceError):
        pol.autotune = "search"
    with pytest.raises(TypeError):
        pol.impl["matmul"] = "jnp"  # MappingProxyType: no mutation
    with pytest.raises(ValueError, match="unknown impl"):
        policy.ExecutionPolicy(impl={"matmul": "fancy"})
    with pytest.raises(ValueError, match="unknown autotune"):
        policy.ExecutionPolicy(autotune="always")
    # programmatic typos must not silently no-op either (cf. parse_impl_arg)
    with pytest.raises(ValueError, match="unknown op"):
        policy.ExecutionPolicy(impl={"atention": "jnp"})
    with pytest.raises(ValueError, match="unknown op"):
        with policy.apply(variants={"matmull": {"backend": "classical"}}):
            pass


def test_with_merges_impl_entries():
    pol = policy.ExecutionPolicy(impl={"*": "jnp", "attention": "pallas"})
    new = pol.with_(impl={"matmul": "pallas"}, autotune="replay")
    assert new.impl_for("attention") == "pallas"  # kept
    assert new.impl_for("matmul") == "pallas"     # merged in
    assert new.impl_for("scan") == "jnp"          # wildcard kept
    assert new.autotune == "replay" and pol.autotune is None  # original intact


# -- the stack ----------------------------------------------------------------

def test_apply_nesting_and_restore_on_exit():
    base = policy.current()
    assert base.impl_for("matmul") == "auto"
    with policy.apply(impl={"matmul": "pallas"}):
        assert policy.current().impl_for("matmul") == "pallas"
        with policy.apply(impl={"attention": "jnp"}):
            # inner scope derives from the outer one: both entries live
            assert policy.current().impl_for("matmul") == "pallas"
            assert policy.current().impl_for("attention") == "jnp"
        assert policy.current().impl_for("attention") == "auto"  # unwound
    assert policy.current().impl_for("matmul") == "auto"

    with pytest.raises(RuntimeError, match="boom"):
        with policy.apply(impl={"matmul": "jnp"}):
            raise RuntimeError("boom")
    assert policy.current().impl_for("matmul") == "auto"  # exception unwinds


def test_scopes_are_thread_isolated():
    seen = {}

    def worker():
        seen["impl"] = policy.current().impl_for("matmul")

    with policy.apply(impl={"matmul": "pallas"}):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
        assert policy.current().impl_for("matmul") == "pallas"
    assert seen["impl"] == "auto"  # fresh thread: ambient, not our scope


def test_resolution_is_trace_time_under_jit():
    """Backend resolution happens while tracing (Python level), so a scope
    around the first call bakes the decision into the compiled function;
    later calls replay it without retracing — per-call positions and other
    traced values never consult the policy again."""
    resolved = []

    @jax.jit
    def f(x):
        resolved.append(registry.resolve("matmul", differentiable=False))
        return x + 1

    with policy.apply(impl={"*": "pallas"}):
        f(jnp.ones((2,)))
    assert resolved == ["pallas"]
    f(jnp.ones((2,)))  # outside the scope: no retrace, baked decision
    assert resolved == ["pallas"]


def test_install_sits_under_scopes():
    try:
        policy.install(policy.ambient().with_(impl={"*": "jnp"}))
        assert policy.current().impl_for("scan") == "jnp"
        with policy.apply(impl={"scan": "pallas"}):
            assert policy.current().impl_for("scan") == "pallas"
        assert policy.current().impl_for("scan") == "jnp"
    finally:
        policy.install(None)
    assert policy.current().impl_for("scan") == "auto"


# -- environment assembly -----------------------------------------------------

def test_ambient_env_assembly(monkeypatch):
    monkeypatch.setenv("REPRO_IMPL", "attention=jnp, *=pallas")
    monkeypatch.setenv("REPRO_STRICT_TILES", "1")
    monkeypatch.setenv("REPRO_INTERPRET", "1")
    amb = policy.ambient()
    assert amb.impl_for("attention") == "jnp"
    assert amb.impl_for("matmul") == "pallas"  # wildcard from env
    assert amb.strict_tiles is True
    assert amb.interpret is True
    monkeypatch.delenv("REPRO_IMPL")
    monkeypatch.delenv("REPRO_STRICT_TILES")
    monkeypatch.delenv("REPRO_INTERPRET")
    amb = policy.ambient()  # env-keyed memo re-assembles
    assert amb.impl_for("attention") == "auto" and amb.strict_tiles is False


def test_impl_grammar():
    assert policy.parse_impl_arg("*=pallas") == {"*": "pallas"}
    assert policy.parse_impl_arg("pallas") == {"*": "pallas"}  # bare backend
    assert policy.parse_impl_arg("attention=jnp,matmul=pallas") == {
        "attention": "jnp", "matmul": "pallas"}
    assert policy.parse_impl_arg("") == {}
    with pytest.raises(ValueError, match="unknown backend"):
        policy.parse_impl_arg("matmul=fancy")
    with pytest.raises(ValueError, match="empty op"):
        policy.parse_impl_arg("=pallas")
    with pytest.raises(ValueError, match="unknown op"):
        policy.parse_impl_arg("attnetion=pallas")  # typo'd op must not no-op


def test_impl_grammar_variant_knobs():
    """The variants extension: ``op=backend:knob=value`` entries carry typed
    per-op knobs alongside the impl map."""
    impl, variants = policy.parse_impl_spec("attention=pallas:kv_dtype=int8")
    assert impl == {"attention": "pallas"}
    assert variants == {"attention": {"kv_dtype": "int8"}}
    impl, variants = policy.parse_impl_spec(
        "matmul=pallas:backend=classical:qkv_fused=true,attention=jnp")
    assert impl == {"matmul": "pallas", "attention": "jnp"}
    assert variants == {"matmul": {"backend": "classical",
                                   "qkv_fused": True}}  # typed: bool
    _, variants = policy.parse_impl_spec("scan=pallas:block=128")
    assert variants == {"scan": {"block": 128}}  # typed: int
    # back-compat: the impl-only parser accepts knobs and drops them
    assert policy.parse_impl_arg("attention=pallas:kv_dtype=int8") == {
        "attention": "pallas"}
    with pytest.raises(ValueError, match="wildcard"):
        policy.parse_impl_spec("*=pallas:kv_dtype=int8")
    with pytest.raises(ValueError, match="knob=value"):
        policy.parse_impl_spec("attention=pallas:kv_dtype")


def test_per_op_interpret_variant(monkeypatch):
    """``--impl 'op=pallas:interpret=true'`` forces interpret mode for ONE
    op through the typed-knob grammar: the knob sits between the explicit
    call arg (stronger) and the policy-global ``interpret`` flag (weaker),
    and never leaks into the kernel's tile kwargs."""
    from repro.kernels.registry import KernelSpec
    seen = []

    def fake_pallas(x, *, interpret, **tiles):
        seen.append((interpret, "interpret" in tiles))
        return x

    monkeypatch.setitem(
        registry._REGISTRY, "scan",
        KernelSpec(name="scan", pallas=fake_pallas, ref=lambda x: x,
                   plan=lambda x: {}, supported=lambda: True))
    _, variants = policy.parse_impl_spec("scan=pallas:interpret=true")
    assert variants == {"scan": {"interpret": True}}  # typed bool

    x = jnp.ones((4,))
    with policy.apply(impl={"scan": "pallas"},
                      variants={"scan": {"interpret": True}}):
        registry.dispatch("scan", x)                       # knob forces on
        registry.dispatch("scan", x, interpret=False)      # explicit wins
    with policy.apply(impl={"scan": "pallas"},
                      variants={"scan": {"interpret": False}},
                      interpret=True):
        registry.dispatch("scan", x)            # knob beats the global flag
    with policy.apply(impl={"scan": "pallas"}):
        registry.dispatch("scan", x)            # no knob: native -> compiled
    assert seen == [(True, False), (False, False), (False, False),
                    (False, False)]


def test_describe_round_trips_variants():
    """describe()'s impl/variant prefix parses back to the same dispatch
    decisions (knob order and bool casing normalize)."""
    spec = "attention=pallas:kv_dtype=int8,matmul=pallas:qkv_fused=true"
    impl, variants = policy.parse_impl_spec(spec)
    pol = policy.ExecutionPolicy(impl=impl, variants=variants)
    rendered = pol.describe()
    impl2, variants2 = policy.parse_impl_spec(rendered)
    assert impl2 == dict(impl)
    assert variants2 == {op: dict(k) for op, k in variants.items()}


def test_ambient_env_carries_variants(monkeypatch):
    monkeypatch.setenv("REPRO_IMPL", "attention=pallas:kv_dtype=int8")
    amb = policy.ambient()
    assert amb.impl_for("attention") == "pallas"
    assert amb.variant_for("attention") == {"kv_dtype": "int8"}
    monkeypatch.delenv("REPRO_IMPL")
    assert policy.ambient().variant_for("attention") == {}


# -- resolver capability gates ------------------------------------------------

def test_resolve_capability_gates():
    with policy.apply(impl={"*": "pallas"}):
        # attention: custom softmax scale / traced window fail the needs gate
        assert registry.resolve("attention") == "pallas"
        assert registry.resolve("attention", softmax_scale=0.3) == "jnp"
        assert registry.resolve("attention",
                                window=jnp.asarray(4)) == "jnp"
        assert registry.resolve("attention", window=128) == "pallas"
        # ops without a registered backward stay jnp for model callers
        assert registry.resolve("scan") == "jnp"
        assert registry.resolve("scan", differentiable=False) == "pallas"
    # explicit jnp/ref force wins over everything
    with policy.apply(impl={"attention": "ref"}):
        assert registry.resolve("attention") == "jnp"


def test_policy_variant_overrides_reach_dispatch():
    x = jax.random.normal(jax.random.key(0), (2, 256))
    with policy.apply(variants={"scan": {"block": 60}}):
        # the policy's variant override reaches the kernel (non-divisor
        # block trips bp_scan's divisibility assert — proof it arrived)
        with pytest.raises(AssertionError):
            registry.dispatch("scan", x, impl="pallas")
        # an explicit call-site kwarg still wins over the policy variant
        out = registry.dispatch("scan", x, impl="pallas", block=64)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(registry.dispatch("scan", x, impl="ref")),
                               rtol=1e-4, atol=1e-4)


def test_policy_autotune_scope(monkeypatch):
    monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)
    assert autotune.mode() == "off"
    with policy.apply(autotune="search"):
        assert autotune.mode() == "search"
        with policy.apply(impl={"matmul": "jnp"}):  # inherits from outer scope
            assert autotune.mode() == "search"
    assert autotune.mode() == "off"


def test_strict_tiles_policy(monkeypatch):
    monkeypatch.delenv("REPRO_STRICT_TILES", raising=False)
    x = jax.random.normal(jax.random.key(0), (2, 256))
    with policy.apply(strict_tiles=True):
        with pytest.raises(ValueError, match="ignored on the"):
            registry.dispatch("scan", x, impl="ref", block=64)
    with warnings.catch_warnings():
        warnings.simplefilter("always")
        registry.dispatch("scan", x, impl="ref", block=64)  # back to warning


@pytest.mark.parametrize("case", ["matmul_snap", "attention_snap",
                                  "needs_gate"])
def test_strict_tiles_forbid_silent_fallbacks(monkeypatch, case):
    """Under strict tiles no call quietly leaves its kernel: a degenerate
    tile snap in ``hbp_matmul`` (prime dim, sub-sublane tile) or in flash
    attention, and the registry's ``needs`` gate, raise instead of running
    ``jnp.dot`` or the oracle."""
    monkeypatch.delenv("REPRO_STRICT_TILES", raising=False)
    key = jax.random.key(0)
    with policy.apply(strict_tiles=True):
        with pytest.raises(ValueError, match="strict tiles"):
            if case == "matmul_snap":
                a = jax.random.normal(key, (31, 31))
                registry.dispatch("matmul", a, a, impl="pallas",
                                  bm=16, bn=16, bk=16)
            elif case == "attention_snap":
                q = jax.random.normal(key, (2, 127, 32))
                registry.dispatch("attention", q, q, q, impl="pallas",
                                  causal=True, q_block=64, kv_block=64)
            else:
                q = jax.random.normal(key, (2, 8, 32))
                with policy.apply(impl={"attention": "pallas"}):
                    registry.dispatch("attention", q, q, q, causal=True,
                                      softmax_scale=0.5)


def test_reset_warnings_rearms_warn_once():
    x = jax.random.normal(jax.random.key(0), (2, 256))
    with pytest.warns(UserWarning, match="ignored on the"):
        registry.dispatch("scan", x, impl="ref", block=64)
    with warnings.catch_warnings():  # second call: silent (warn-once)
        warnings.simplefilter("error")
        registry.dispatch("scan", x, impl="ref", block=64)
    registry.reset_warnings()
    with pytest.warns(UserWarning, match="ignored on the"):
        registry.dispatch("scan", x, impl="ref", block=64)


# -- RunOptions compat shim parity (acceptance bar) ---------------------------

FORCED = {"attention": "pallas", "matmul": "pallas"}


def _models(arch):
    cfg = dataclasses.replace(get_smoke_config(arch), dtype="float32")
    shim = build_model(cfg, RunOptions(remat="none", attention_impl="pallas",
                                       matmul_impl="pallas"))
    plain = build_model(cfg, RunOptions(remat="none"))
    return cfg, shim, plain


def _greedy(model, params, prompt, scope, steps=3, max_len=16):
    with scope:
        logits, cache = jax.jit(
            lambda p, t: model.prefill(p, t, max_len))(params, {"tokens": prompt})
        dec = jax.jit(model.decode_step)
        cur = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        out = []
        for i in range(steps):
            out.append(np.asarray(cur[:, 0]))
            logits, cache = dec(params, cur, jnp.int32(prompt.shape[1] + i), cache)
            cur = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    return np.stack(out)


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "recurrentgemma-2b"])
def test_shim_matches_policy_greedy_decode(arch):
    """The deprecated RunOptions knobs and the equivalent ExecutionPolicy
    scope produce identical greedy-decode tokens (dense + hybrid)."""
    cfg, shim, plain = _models(arch)
    params = shim.init(jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(1), (2, 6), 3, cfg.vocab_size)
    a = _greedy(shim, params, prompt, contextlib.nullcontext())
    b = _greedy(plain, params, prompt, policy.apply(impl=FORCED))
    np.testing.assert_array_equal(a, b)
    # and the forced route really differs from the all-jnp route upstream
    # decisions-wise: resolve flips under the scope
    with policy.apply(impl=FORCED):
        assert registry.resolve("matmul") == "pallas"
    assert registry.resolve("matmul") == "jnp"


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "recurrentgemma-2b"])
def test_shim_matches_policy_train_step(arch):
    """Loss and grads of one train step are identical between the shim and
    the equivalent policy scope."""
    cfg, shim, plain = _models(arch)
    params = shim.init(jax.random.key(0))
    batch = {
        "tokens": jax.random.randint(jax.random.key(2), (2, 16), 3, cfg.vocab_size),
        "labels": jax.random.randint(jax.random.key(3), (2, 16), 0, cfg.vocab_size),
    }
    la, ga = jax.value_and_grad(shim.loss)(params, batch)
    with policy.apply(impl=FORCED):
        lb, gb = jax.value_and_grad(plain.loss)(params, batch)
    np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    for a, b in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_hybrid_ring_buffer_kernel_route_keeps_decode_exact():
    """The ring-buffer decode cache no longer pins itself to jnp: under a
    forced-pallas policy the RingKV layout maps its wrapped rows onto the
    flash kernel's per-row q_offset/kv_len vectors, and windowed decode
    with the rotated cache still matches the same model decoding over the
    full linear cache."""
    cfg = dataclasses.replace(get_smoke_config("recurrentgemma-2b"),
                              dtype="float32")
    ring = build_model(cfg, RunOptions(remat="none", windowed_decode_cache=True))
    full = build_model(cfg, RunOptions(remat="none"))
    params = ring.init(jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(1), (1, 12), 3, cfg.vocab_size)
    scope = policy.apply(impl=FORCED)
    a = _greedy(ring, params, prompt, scope, steps=4, max_len=24)
    b = _greedy(full, params, prompt, policy.apply(impl=FORCED), steps=4,
                max_len=24)
    np.testing.assert_array_equal(a, b)


def test_expert_project_routes_through_registry():
    """MoE expert matmuls under a pallas policy: the registry matmul vmapped
    over the expert axis matches the batched einsum, forward and grads (the
    matmul custom VJP under vmap)."""
    from repro.models import common

    h = jax.random.normal(jax.random.key(0), (2, 4, 16, 32))  # (g, E, C, d)
    w = jax.random.normal(jax.random.key(1), (4, 32, 24))     # (E, d, f)
    want = common.expert_project(h, w)  # ambient on CPU: the jnp einsum
    gj = jax.grad(lambda a, b: common.expert_project(a, b).sum(),
                  argnums=(0, 1))(h, w)
    with policy.apply(impl={"matmul": "pallas"}):
        got = common.expert_project(h, w)
        gp = jax.grad(lambda a, b: common.expert_project(a, b).sum(),
                      argnums=(0, 1))(h, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)
    for a, b in zip(gp, gj):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


# -- simulator namespace ------------------------------------------------------

def test_simulator_namespace_shares_op_names():
    """core/algorithms program builders are reachable under the kernel op
    names, so simulator cost cross-checks and KernelSpec lookups share one
    namespace."""
    from repro.core.hbp import BPProgram

    prog = registry.simulator_program("matmul", 8)
    assert isinstance(prog, BPProgram) and prog.name == "strassen"
    scan_progs = registry.simulator_program("scan", 16)
    assert [p.name for p in scan_progs] == ["msum", "psdist"]
    assert registry.simulator_program("transpose", 8).name == "mtbi"
    assert isinstance(registry.simulator_program("fft", 64), BPProgram)
    with pytest.raises(KeyError, match="no registered simulator"):
        registry.simulator_program("attention", 8)
    # one namespace: every simulator-bearing op is a registered kernel op
    sims = [n for n in registry.names() if registry.get(n).simulator]
    assert sims == ["fft", "matmul", "scan", "transpose"]
