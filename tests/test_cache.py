"""DecodeCache layouts: RingKV wrap-around kernel parity + layering.

The RingKV layout maps a wrapped window buffer onto the flash kernel's
per-row ``q_offset``/``kv_len`` SMEM vectors (raw slots + causal softmax
permutation-invariance); these tests pin that mapping against a scalar
python loop that gathers each row's live window in chronological order —
per-row cursors at non-tile-aligned depths, wrapped and unwrapped rows in
one launch, both the pallas and jnp routes.  The kernel's ``kv_len == 0``
exact-zero contract and the int8 LinearKV scale path get the same oracle
treatment.  A source-level layering test keeps cache mutation idioms out
of the family modules — every slab write must go through
``repro.models.cache``.
"""
import inspect
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import policy
from repro.models import cache as dcache
from repro.models import common


def _rng_kv(rng, b, n, kvh, hd):
    return rng.standard_normal((b, n, kvh, hd)).astype(np.float32)


def _scalar_oracle(q, k_rows, v_rows):
    """One row, one query token: python-loop softmax over the row's keys in
    chronological order.  q (h, hd); k_rows/v_rows (n, kvh, hd)."""
    h, hd = q.shape
    kvh = k_rows.shape[1]
    group = h // kvh
    out = np.zeros((h, hd), np.float32)
    for hh in range(h):
        kk = k_rows[:, hh // group]            # (n, hd)
        vv = v_rows[:, hh // group]
        scores = kk @ q[hh] / np.sqrt(hd)
        scores = scores - scores.max()
        p = np.exp(scores)
        p /= p.sum()
        out[hh] = p @ vv
    return out


def _ring_fill(rng, b, cap, kvh, hd, positions):
    """Build ring slabs holding each row's live window: token at position
    ``p`` sits in slot ``p % cap``; dead slots hold garbage."""
    k = rng.standard_normal((b, cap, kvh, hd)).astype(np.float32) * 100.0
    v = rng.standard_normal((b, cap, kvh, hd)).astype(np.float32) * 100.0
    tok_k, tok_v = {}, {}
    for row, last in enumerate(positions):
        n = min(last + 1, cap)
        for p in range(last + 1 - n, last + 1):
            tok_k[(row, p)] = rng.standard_normal((kvh, hd)).astype(np.float32)
            tok_v[(row, p)] = rng.standard_normal((kvh, hd)).astype(np.float32)
            k[row, p % cap] = tok_k[(row, p)]
            v[row, p % cap] = tok_v[(row, p)]
    return k, v, tok_k, tok_v


# positions: wrapped at non-tile-aligned cursors (37, 53), exactly-full
# (31), partial (5), and a fresh row (0) — one launch, per-row vectors
RING_POSITIONS = [37, 5, 31, 0, 53, 12, 40, 7]


@pytest.mark.parametrize("route", ["pallas", "jnp"])
def test_ringkv_wrap_matches_scalar_oracle(route):
    """The decode attend over a RingKV slab — kernel route via per-row
    q_offset/kv_len, jnp route via slot_positions masking — equals the
    scalar chronological-gather oracle on every row, wrapped or not."""
    rng = np.random.default_rng(0)
    b, cap, h, kvh, hd = len(RING_POSITIONS), 32, 4, 2, 64
    positions = np.asarray(RING_POSITIONS, np.int32)
    k, v, tok_k, tok_v = _ring_fill(rng, b, cap, kvh, hd, positions)
    kv = dcache.RingKV(k=jnp.asarray(k), v=jnp.asarray(v),
                       pos=jnp.asarray(positions), b_axis=0)
    q = rng.standard_normal((b, 1, h, hd)).astype(np.float32)

    with policy.apply(impl={"attention": route if route == "pallas"
                            else "jnp"}):
        out = common.attention(
            jnp.asarray(q), kv.k, kv.v, jnp.asarray(positions)[:, None],
            kv.slot_positions(positions), causal=True, window=None,
            kv_len=kv.attend_lens(positions))
    out = np.asarray(out)

    for row, last in enumerate(positions):
        n = min(last + 1, cap)
        ps = range(last + 1 - n, last + 1)
        k_rows = np.stack([tok_k[(row, p)] for p in ps])
        v_rows = np.stack([tok_v[(row, p)] for p in ps])
        want = _scalar_oracle(q[row, 0], k_rows, v_rows)
        np.testing.assert_allclose(out[row, 0], want, rtol=2e-4, atol=2e-5,
                                   err_msg=f"row {row} pos {last}")


def test_kernel_zero_length_rows_emit_exact_zeros():
    """The flash kernel's per-row contract: a lane with ``kv_len == 0``
    attends nothing and emits EXACT zeros (the l_safe guard), while its
    neighbours in the same launch are untouched."""
    rng = np.random.default_rng(1)
    b, s, h, kvh, hd = 4, 16, 4, 2, 64
    q = jnp.asarray(rng.standard_normal((b, 1, h, hd)).astype(np.float32))
    k = jnp.asarray(_rng_kv(rng, b, s, kvh, hd))
    v = jnp.asarray(_rng_kv(rng, b, s, kvh, hd))
    kv_len = jnp.asarray([5, 0, 16, 0], jnp.int32)
    q_pos = jnp.maximum(kv_len - 1, 0)[:, None]
    with policy.apply(impl={"attention": "pallas"}):
        out = np.asarray(common.attention(
            q, k, v, q_pos, jnp.arange(s, dtype=jnp.int32), causal=True,
            kv_len=kv_len))
    assert np.all(out[1] == 0.0) and np.all(out[3] == 0.0)
    assert np.all(np.isfinite(out)) and np.any(out[0] != 0.0)


def test_linearkv_int8_scales_match_dequant_oracle():
    """Int8 LinearKV decode through the kernel's in-block dequant at ragged
    per-row depths equals the scalar oracle over the up-front-dequantized
    slab."""
    rng = np.random.default_rng(2)
    b, s, h, kvh, hd = 4, 32, 4, 2, 64
    kf = _rng_kv(rng, b, s, kvh, hd)
    vf = _rng_kv(rng, b, s, kvh, hd)
    k_scale = np.abs(kf).max(axis=(1, 3)) / 127.0         # (b, kvh)
    v_scale = np.abs(vf).max(axis=(1, 3)) / 127.0
    k8 = np.clip(np.round(kf / k_scale[:, None, :, None]), -127, 127)
    v8 = np.clip(np.round(vf / v_scale[:, None, :, None]), -127, 127)
    pos = np.asarray([31, 3, 17, 0], np.int32)            # ragged depths
    q = rng.standard_normal((b, 1, h, hd)).astype(np.float32)
    with policy.apply(impl={"attention": "pallas"}):
        out = np.asarray(common.attention(
            jnp.asarray(q), jnp.asarray(k8, jnp.int8),
            jnp.asarray(v8, jnp.int8), jnp.asarray(pos)[:, None],
            jnp.arange(s, dtype=jnp.int32), causal=True,
            k_scale=jnp.asarray(k_scale, jnp.float32),
            v_scale=jnp.asarray(v_scale, jnp.float32)))
    kd = k8 * k_scale[:, None, :, None]
    vd = v8 * v_scale[:, None, :, None]
    for row in range(b):
        n = pos[row] + 1
        want = _scalar_oracle(q[row, 0], kd[row, :n], vd[row, :n])
        np.testing.assert_allclose(out[row, 0], want, rtol=2e-4, atol=2e-5,
                                   err_msg=f"row {row}")


def test_ring_write_places_and_wraps():
    """ring_write lands position ``p`` in slot ``p % C`` for per-row
    offsets, and an over-capacity write keeps exactly the last C tokens."""
    b, cap, kvh, hd = 3, 8, 1, 4
    slab = jnp.zeros((b, cap, kvh, hd))
    s = 5
    new = jnp.arange(b * s * kvh * hd, dtype=jnp.float32).reshape(
        b, s, kvh, hd)
    wa = jnp.asarray([0, 6, 13], jnp.int32)  # linear, wrapping, wrapped
    got = np.asarray(dcache.ring_write(slab, new, wa))
    for row, w in enumerate([0, 6, 13]):
        for j in range(s):
            np.testing.assert_array_equal(got[row, (w + j) % cap],
                                          np.asarray(new)[row, j])
    # s >= C: only the last C tokens survive, at their true slots
    big = jnp.arange(b * 11 * kvh * hd, dtype=jnp.float32).reshape(
        b, 11, kvh, hd)
    got = np.asarray(dcache.ring_write(slab, big, jnp.zeros((b,), jnp.int32)))
    for j in range(11 - cap, 11):
        np.testing.assert_array_equal(got[0, j % cap], np.asarray(big)[0, j])


def test_ringkv_slot_positions_and_attend_lens():
    kv = dcache.RingKV(k=jnp.zeros((2, 4, 1, 2)), v=jnp.zeros((2, 4, 1, 2)),
                       pos=jnp.asarray([5, 1], jnp.int32), b_axis=0)
    sp = np.asarray(kv.slot_positions(kv.pos))
    np.testing.assert_array_equal(sp[0], [4, 5, 2, 3])
    big = 1 << 30
    np.testing.assert_array_equal(sp[1], [0, 1, big, big])
    np.testing.assert_array_equal(np.asarray(kv.attend_lens(kv.pos)), [4, 2])


# -- snapshot/restore: the fault-recovery row pair ----------------------------

def _rand_composite(rng, b=3):
    """One composite cache exercising every layout: quantized LinearKV with
    a layer lead axis, RingKV with row 1 at a WRAPPED cursor (pos 13 >
    capacity 8), frozen CrossKV, and StateCarry with mixed validity."""
    def f(*s):
        return jnp.asarray(rng.standard_normal(s).astype(np.float32))
    return {
        "attn": dcache.LinearKV(k=f(2, b, 16, 2, 4), v=f(2, b, 16, 2, 4),
                                pos=jnp.asarray([3, 9, 16], jnp.int32),
                                k_scale=f(2, b, 2), v_scale=f(2, b, 2),
                                b_axis=1),
        "win": dcache.RingKV(k=f(b, 8, 1, 4), v=f(b, 8, 1, 4),
                             pos=jnp.asarray([2, 13, 8], jnp.int32),
                             b_axis=0),
        "cross": dcache.CrossKV(k=f(b, 6, 1, 4), v=f(b, 6, 1, 4), b_axis=0),
        "ssm": dcache.StateCarry(states={"h": f(2, b, 5),
                                         "conv": f(1, b, 3, 4)},
                                 valid=jnp.asarray([True, False, True])),
    }


def _assert_tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_snapshot_restore_row_roundtrip_all_layouts():
    """snapshot_row/restore_row carry EVERY per-row fact — k/v slabs, write
    cursors (including a wrapped ring cursor), int8 scales, frozen cross-KV,
    recurrent state and its validity flag — and touch only their row: after
    corrupting row 1 wholesale, restoring its snapshot reproduces the
    original composite leaf-for-leaf."""
    cache = _rand_composite(np.random.default_rng(5))
    snap = dcache.snapshot_row(cache, 1)
    # host-staged: numpy leaves, no live device buffers in the resume point
    assert all(isinstance(x, np.ndarray) for x in jax.tree.leaves(snap))
    assert int(snap["win"].pos[0]) == 13          # wrapped absolute cursor
    corrupt = dcache.set_slot(cache, 1,
                              jax.tree.map(jnp.zeros_like, snap))
    assert not np.array_equal(np.asarray(corrupt["attn"].k),
                              np.asarray(cache["attn"].k))
    _assert_tree_equal(dcache.restore_row(corrupt, 1, snap), cache)


def test_snapshot_restores_into_different_slot():
    """Row slices carry no slot identity: a snapshot of slot 1 restored
    into slot 0 of a fresh cache reproduces slot 1's state there (the
    engine re-admits recovered requests into whichever slot matches)."""
    rng = np.random.default_rng(6)
    cache = _rand_composite(rng)
    snap = dcache.snapshot_row(cache, 1)
    fresh = jax.tree.map(jnp.zeros_like, _rand_composite(rng))
    moved = dcache.restore_row(fresh, 0, snap)
    _assert_tree_equal(dcache.slot(moved, 0), dcache.slot(cache, 1))
    # the other rows of the fresh cache stay zero
    _assert_tree_equal(dcache.slot(moved, 2),
                       jax.tree.map(jnp.zeros_like, dcache.slot(cache, 2)))


def test_snapshot_compatible_gates_cross_replica_restore():
    """The cross-replica portability gate the router leans on: a snapshot
    restores into any same-config cache (accepted silently, eval_shape
    only), while a different sequence capacity, a different KV dtype, or a
    missing layout fails loudly with the mismatch named — never a corrupt
    row."""
    rng = np.random.default_rng(7)
    cache = _rand_composite(rng)
    snap = dcache.snapshot_row(cache, 1)
    dcache.snapshot_compatible(cache, snap)     # same config: no raise
    # shorter sequence axis, as from a replica built with a smaller max_len
    short = jax.tree.map(
        lambda x: x[:, :-1] if np.ndim(x) >= 2 and x.shape[1] > 1 else x,
        snap)
    with pytest.raises(ValueError, match="shape"):
        dcache.snapshot_compatible(cache, short)
    # quantization mismatch: f32 snapshot leaves downcast to f16
    half = jax.tree.map(
        lambda x: np.asarray(x, np.float16)
        if np.asarray(x).dtype == np.float32 else x, snap)
    with pytest.raises(ValueError, match="dtype"):
        dcache.snapshot_compatible(cache, half)
    # structural mismatch: a layout missing from the composite
    with pytest.raises(ValueError, match="layout"):
        dcache.snapshot_compatible(
            cache, {k: v for k, v in snap.items() if k != "attn"})


# -- layering: slab mutation stays inside repro.models.cache ------------------

_FORBIDDEN = [
    (r"dynamic_update_slice", "raw dynamic_update_slice on cache slabs"),
    (r"jnp\.roll", "ring maintenance must use cache.ring_write"),
    (r"""["']k["']\s*:""", "raw cache dict entry 'k'"),
    (r"""["']v["']\s*:""", "raw cache dict entry 'v'"),
    (r"""["']xk["']\s*:""", "raw cache dict entry 'xk'"),
    (r"""["']img_k["']\s*:""", "raw cache dict entry 'img_k'"),
]


def test_family_modules_never_mutate_cache_slabs_directly():
    """Every model family goes through the DecodeCache layouts and the
    cache-module write helpers: no family source constructs raw k/v cache
    dict entries or hand-rolls slab writes."""
    from repro.models import dense, encdec, hybrid, ssm, vlm
    for mod in (dense, hybrid, ssm, encdec, vlm):
        src = inspect.getsource(mod)
        for pat, why in _FORBIDDEN:
            hits = [ln + 1 for ln, line in enumerate(src.splitlines())
                    if re.search(pat, line)]
            assert not hits, (
                f"{mod.__name__} line(s) {hits}: {why} — route it through "
                f"repro.models.cache")


# -- incremental snapshots: RowCopier against the whole-row copy --------------

def _layouts_of(tree):
    """The layouts of a composite, in a fixed order."""
    if isinstance(tree, dict):
        return [lo for k in sorted(tree) for lo in _layouts_of(tree[k])]
    return [tree]


def _incremental_cache(kind, rng, b=3):
    """A cache of one layout kind (or the all-layout composite) whose row
    1 stands at cursor 4, every slab position filled with noise."""
    def f(*s):
        return jnp.asarray(rng.standard_normal(s).astype(np.float32))
    pos = jnp.asarray([2, 4, 9], jnp.int32)
    if kind == "dense":
        return dcache.LinearKV(k=f(2, b, 16, 2, 4), v=f(2, b, 16, 2, 4),
                               pos=pos, k_scale=None, v_scale=None, b_axis=1)
    if kind == "int8":
        def q(*s):
            return jnp.asarray(rng.integers(-127, 128, s), jnp.int8)
        return dcache.LinearKV(k=q(b, 16, 2, 4), v=q(b, 16, 2, 4), pos=pos,
                               k_scale=f(b, 2), v_scale=f(b, 2), b_axis=0)
    if kind == "ring":
        return dcache.RingKV(k=f(b, 8, 1, 4), v=f(b, 8, 1, 4), pos=pos,
                             b_axis=0)
    if kind == "state":
        return dcache.StateCarry(states={"h": f(2, b, 5),
                                         "conv": f(1, b, 3, 4)},
                                 valid=jnp.asarray([True, True, False]))
    cache = _rand_composite(rng, b)
    cache["attn"] = cache["attn"].replace(pos=pos)
    return cache


def _decode_everywhere(cache, rng):
    """One decode step of every row: each positional layout writes fresh
    noise at each row's own cursor (none past a LinearKV's capacity, a
    RingKV wraps) and advances it; recurrent state and int8 scales are
    redrawn, so a copy that skipped them would show."""
    def step(lo):
        if isinstance(lo, (dcache.LinearKV, dcache.RingKV)):
            k, v = np.array(lo.k), np.array(lo.v)
            cap = lo.capacity
            pos = np.array(lo.pos)
            for r, p in enumerate(pos):
                if isinstance(lo, dcache.LinearKV) and p >= cap:
                    continue
                at = (slice(None),) * lo.b_axis + (r, p % cap)
                k[at] = rng.standard_normal(k[at].shape).astype(k.dtype)
                v[at] = rng.standard_normal(v[at].shape).astype(v.dtype)
                pos[r] = p + 1
            new = lo.replace(k=jnp.asarray(k), v=jnp.asarray(v),
                             pos=jnp.asarray(pos))
            if isinstance(lo, dcache.LinearKV) and lo.quantized:
                new = new.replace(
                    k_scale=jnp.asarray(rng.random(lo.k_scale.shape),
                                        jnp.float32),
                    v_scale=jnp.asarray(rng.random(lo.v_scale.shape),
                                        jnp.float32))
            return new
        if isinstance(lo, dcache.StateCarry):
            return lo.replace(states={
                k: jnp.asarray(rng.standard_normal(a.shape), a.dtype)
                for k, a in lo.states.items()})
        return lo
    if isinstance(cache, dict):
        return {k: step(v) for k, v in cache.items()}
    return step(cache)


def _assert_row_live_equal(got, want, pos):
    """Rows equal on everything a restore resumes from: LinearKV slabs
    below ``pos``, and every other leaf exactly."""
    for g, w in zip(_layouts_of(got), _layouts_of(want)):
        assert type(g) is type(w)
        if isinstance(g, dcache.LinearKV):
            live = (slice(None),) * (g.b_axis + 1) + (slice(0, pos),)
            for a, b in ((g.k, w.k), (g.v, w.v)):
                np.testing.assert_array_equal(np.asarray(a)[live],
                                              np.asarray(b)[live])
            g, w = g.replace(k=None, v=None), w.replace(k=None, v=None)
        _assert_tree_equal(g, w)


@pytest.mark.parametrize("points", [[7, 10, 13, 16], [7, 10, 13, 15, 16]],
                         ids=["cadence", "clamped-tail"])
@pytest.mark.parametrize("kind",
                         ["dense", "int8", "ring", "state", "composite"])
def test_incremental_snapshot_equals_full_snapshot(kind, points):
    """A row written across several snapshot points: at each, the
    incremental copy (a first one from position 0, then only the positions
    written since the previous, into its buffers) equals the whole-row
    ``snapshot_row`` below the cursor, with cursors, scales and every
    other layout exact, and restores into a different slot as the row.
    A delta moves one block of the narrow width; in ``clamped-tail`` the
    last delta, [15, 16), is read as the block [13, 16) at the capacity."""
    rng = np.random.default_rng(11)
    cache = _incremental_cache(kind, rng)
    copier = dcache.RowCopier((5, 3))
    row, pos, prev = 1, 4, None
    for upto in points:
        while pos < upto:
            cache = _decode_everywhere(cache, rng)
            pos += 1
        full = dcache.snapshot_row(cache, row)
        got, moved = copier.copy(cache, row, pos,
                                 *((prev, since) if prev else ()))
        assert all(isinstance(x, np.ndarray) for x in jax.tree.leaves(got))
        _assert_row_live_equal(got, full, pos)
        slabs = [lo for lo in _layouts_of(full)
                 if isinstance(lo, dcache.LinearKV)]
        head = sum(x.nbytes for lo in _layouts_of(full)
                   for x in jax.tree.leaves(
                       lo.replace(k=None, v=None)
                       if isinstance(lo, dcache.LinearKV) else lo))
        per_pos = sum((lo.k.nbytes + lo.v.nbytes) // lo.capacity
                      for lo in slabs)
        if prev is None:
            assert moved == head + per_pos * 8   # blocks [0, 5) and [5, 8)
        else:
            assert moved == head + per_pos * 3   # one narrow block
            for g, p in zip(_layouts_of(got), _layouts_of(prev)):
                if isinstance(g, dcache.LinearKV):  # written in place
                    assert g.k is p.k and g.v is p.v
        moved_to = dcache.restore_row(
            jax.tree.map(jnp.zeros_like, cache), 0, got)
        _assert_row_live_equal(jax.device_get(dcache.slot(moved_to, 0)),
                               full, pos)
        prev, since = got, pos


def test_row_copier_builds_one_program_per_width():
    """The copies take the slot and the start as traced scalars: copying
    every slot at many starts uses the programs :meth:`RowCopier.compile`
    built, one per block width plus the whole-copied part, and no other."""
    cache = _incremental_cache("composite", np.random.default_rng(12))
    copier = dcache.RowCopier((5, 3))
    copier.compile(cache)
    built = dict(copier._programs)
    assert len(built) == 3
    for i in range(3):
        for upto in (1, 4, 9, 16):
            copier.copy(cache, i, upto)
    assert copier._programs == built
