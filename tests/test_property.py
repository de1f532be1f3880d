"""Hypothesis property tests on system invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dep: skip cleanly when absent
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import layouts, planner
from repro.launch.hlo_analysis import shape_bytes
from repro.launch.mesh import make_mesh
from repro.models.moe_layer import SUBLANE, gapped_capacity


# -- planner: the balance condition as a hard invariant -------------------------

@given(st.lists(st.integers(1, 6), min_size=1, max_size=3),
       st.sampled_from(["wq", "wo", "embed", "e_gate", "ln1", "unknown_leaf"]))
@settings(max_examples=40, deadline=None)
def test_planner_never_emits_indivisible_specs(dim_pows, name):
    mesh = make_mesh((1, 1), ("data", "model"))
    shape = tuple(2 ** p for p in dim_pows)
    tree = {name: jax.ShapeDtypeStruct(shape, jnp.float32)}
    specs = planner.plan_params(tree, mesh)
    spec = specs[name]
    assert len(spec) == len(shape)
    for dim, entry in zip(shape, spec):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        assert dim % size == 0


# -- BI layout bijection ---------------------------------------------------------

@given(st.integers(1, 6))
@settings(max_examples=6, deadline=None)
def test_bi_perm_is_bijection(p):
    n = 2 ** p
    perm = layouts.rm_to_bi_perm(n)
    assert len(np.unique(perm)) == n * n


# -- gapping quanta ----------------------------------------------------------------

@given(st.integers(1, 100_000), st.integers(1, 256), st.integers(1, 16),
       st.floats(0.1, 4.0))
@settings(max_examples=60, deadline=None)
def test_gapped_capacity_invariants(n, e, k, cf):
    c = gapped_capacity(n, e, k, cf)
    assert c % SUBLANE == 0
    assert c >= SUBLANE
    # capacity covers the expected per-expert load under balance
    assert c * e >= min(n * k * cf, n * k) * 0.5 or c == SUBLANE


# -- prefix sums associativity (the BP combine) --------------------------------------

@given(st.integers(2, 400), st.integers(0, 5))
@settings(max_examples=25, deadline=None)
def test_two_pass_scan_equals_sequential(n, seed):
    from repro.core.algorithms_jax import prefix_sums

    x = jnp.asarray(np.random.default_rng(seed).standard_normal(n), jnp.float32)
    for block in (7, 64):
        np.testing.assert_allclose(prefix_sums(x, block=block), jnp.cumsum(x),
                                   rtol=2e-4, atol=2e-4)


# -- HLO shape parsing ---------------------------------------------------------------

@given(st.sampled_from(["f32", "bf16", "s32", "pred", "u8"]),
       st.lists(st.integers(1, 64), min_size=0, max_size=4))
@settings(max_examples=40, deadline=None)
def test_shape_bytes_roundtrip(dtype, dims):
    widths = {"f32": 4, "bf16": 2, "s32": 4, "pred": 1, "u8": 1}
    n = 1
    for d in dims:
        n *= d
    s = f"{dtype}[{','.join(map(str, dims))}]"
    assert shape_bytes(s) == n * widths[dtype]


# -- data pipeline determinism across instances ----------------------------------------

@given(st.integers(0, 50), st.integers(0, 3))
@settings(max_examples=10, deadline=None)
def test_batch_at_pure(step, seed):
    from repro.configs import get_smoke_config
    from repro.data import DataConfig, SyntheticLMDataset

    cfg = get_smoke_config("qwen3-1.7b")
    a = SyntheticLMDataset(DataConfig(seed=seed, global_batch=2, seq_len=32), cfg)
    b = SyntheticLMDataset(DataConfig(seed=seed, global_batch=2, seq_len=32), cfg)
    np.testing.assert_array_equal(a.batch_at(step)["tokens"], b.batch_at(step)["tokens"])
