"""DecodeCache: per-row decode-cache state for every model family.

One abstraction replaces five bespoke cache pytrees.  Each *layout* is a
registered-dataclass pytree whose single source of truth for "how deep is
this row's context" is a per-row ``(b,)`` vector — the same vectors the
flash-decode kernel consumes as its per-row ``q_offset``/``kv_len`` SMEM
lanes (``repro.kernels.flash_attention``).  A model family composes its
cache from these layouts (a layout instance, or a dict of them); the
serving engine stays layout-generic by talking only to the module-level
composite helpers (:func:`slot`, :func:`set_slot`, :func:`reset_row`,
:func:`set_row_valid`, :func:`lengths`, and the fault-recovery pair
:func:`snapshot_row`/:func:`restore_row`).

Layouts
-------

``LinearKV``
    Dense/vlm/encdec self-attention: contiguous k/v slabs with the batch at
    a layout-static axis (dense/encdec stack layers in front, vlm stacks
    (superblock, self-layer)), an optional int8 quantization (per-(batch,
    kv-head) f32 scales ride alongside), and the per-row ``pos`` write
    cursor.  Absorbs the old ``common.cache_write``.

``RingKV``
    Hybrid's windowed decode buffer: capacity ``C = min(max_len, window)``
    slots, position ``p`` lives in slot ``p % C``.  Per-row absolute write
    cursors; the wrap-aware mapping into the kernel's per-row vectors is
    :meth:`RingKV.attend_lens` (``kv_len = min(pos + 1, C)``) with
    ``q_offset = pos`` — an unwrapped row is a contiguous prefix, a wrapped
    row attends all ``C`` slots (softmax is permutation-invariant and every
    live slot is inside the window, so slot order never matters).  The jnp
    oracle route gets true positions from :meth:`RingKV.slot_positions`.

``CrossKV``
    Encoder-decoder cross-attention k/v (and the vlm image k/v): written
    once per request at its first prefill chunk, frozen afterwards —
    position-free, so only row isolation matters.

``StateCarry``
    ssm/hybrid recurrent state (conv tails, LRU hidden state, SSD state):
    position-free, with a per-row ``valid`` mask so rows reset
    independently when a slot is reused — decode updates select
    ``where(valid, new, old)`` via :func:`masked_rows`, prefill chunks mask
    by their per-row valid-token counts instead.

Mutation helpers (:func:`linear_write`, :func:`ring_write`,
:func:`masked_rows`, :func:`conv_tail`, :func:`pick_last`) are the ONLY
sanctioned ways a model family touches cache storage — a layering test
greps the family sources for raw ``dynamic_update_slice_in_dim`` / ad-hoc
cache dicts (``tests/test_cache.py``).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def _register(cls, data_fields, meta_fields=()):
    jax.tree_util.register_dataclass(cls, list(data_fields), list(meta_fields))
    return cls


def _slice_axis(a, axis, i):
    # a dynamic slice: ``i`` may be traced, so one program serves every slot
    return jax.lax.dynamic_slice_in_dim(a, i, 1, axis=axis)


def _set_axis(a, axis, i, sub):
    idx = tuple(slice(None) if ax != axis else slice(i, i + 1)
                for ax in range(a.ndim))
    return a.at[idx].set(sub)


# ---------------------------------------------------------------------------
# layouts
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class LinearKV:
    """Contiguous k/v slabs; batch at static axis ``b_axis``, sequence at
    ``b_axis + 1``.  ``pos`` (b,) int32 is each row's context depth == its
    next write position."""

    k: jax.Array                      # (*lead, b, S, kvh, hd)
    v: jax.Array
    pos: jax.Array                    # (b,) int32
    k_scale: Optional[jax.Array]      # (*lead, b, kvh) f32 | None
    v_scale: Optional[jax.Array]
    b_axis: int

    @classmethod
    def create(cls, lead, batch, seq, kv_heads, head_dim, dtype, *,
               quantized=False, b_axis=None):
        shape = tuple(lead) + (batch, seq, kv_heads, head_dim)
        b_axis = len(lead) if b_axis is None else b_axis
        # two distinct buffers: donated jits reject aliased pytree leaves
        def scale():
            return (jnp.ones(tuple(lead) + (batch, kv_heads), jnp.float32)
                    if quantized else None)
        return cls(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   pos=jnp.zeros((batch,), jnp.int32),
                   k_scale=scale(), v_scale=scale(), b_axis=b_axis)

    @property
    def capacity(self) -> int:
        return self.k.shape[self.b_axis + 1]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    def replace(self, **kw) -> "LinearKV":
        return dataclasses.replace(self, **kw)

    def slot(self, i: int) -> "LinearKV":
        sc = (None if self.k_scale is None
              else _slice_axis(self.k_scale, self.b_axis, i))
        vc = (None if self.v_scale is None
              else _slice_axis(self.v_scale, self.b_axis, i))
        return self.replace(k=_slice_axis(self.k, self.b_axis, i),
                            v=_slice_axis(self.v, self.b_axis, i),
                            pos=_slice_axis(self.pos, 0, i), k_scale=sc,
                            v_scale=vc)

    def set_slot(self, i: int, sub: "LinearKV") -> "LinearKV":
        ks = (None if self.k_scale is None
              else _set_axis(self.k_scale, self.b_axis, i, sub.k_scale))
        vs = (None if self.v_scale is None
              else _set_axis(self.v_scale, self.b_axis, i, sub.v_scale))
        return self.replace(k=_set_axis(self.k, self.b_axis, i, sub.k),
                            v=_set_axis(self.v, self.b_axis, i, sub.v),
                            pos=self.pos.at[i:i + 1].set(sub.pos),
                            k_scale=ks, v_scale=vs)

    def reset_row(self, i: int) -> "LinearKV":
        # slabs need no zeroing — writes are position-exact and nothing
        # attends past the row's pos (the per-row kv_len masks it)
        return self.replace(pos=self.pos.at[i].set(0))

    def lengths(self) -> jax.Array:
        return self.pos

    def block_range(self, start: int, width: int) -> slice:
        """The positions :meth:`block` reads for a block asked at
        ``start``: ``width`` capped at the capacity, and a block that would
        cross the capacity read at its end instead."""
        width = min(width, self.capacity)
        start = min(start, self.capacity - width)
        return slice(start, start + width)

    def block(self, i, start, width: int):
        """Row ``i``'s k and v slabs at :meth:`block_range`, ``(*lead, 1,
        width, kvh, hd)`` each; ``i`` and ``start`` may be traced (the
        dynamic slice clamps the start as :meth:`block_range` does)."""
        width = min(width, self.capacity)
        idx = [0] * self.k.ndim
        idx[self.b_axis], idx[self.b_axis + 1] = i, start
        size = list(self.k.shape)
        size[self.b_axis], size[self.b_axis + 1] = 1, width
        return (jax.lax.dynamic_slice(self.k, idx, size),
                jax.lax.dynamic_slice(self.v, idx, size))


_register(LinearKV, ("k", "v", "pos", "k_scale", "v_scale"), ("b_axis",))


@dataclass(frozen=True)
class RingKV:
    """Windowed ring buffer: capacity ``C`` slots at axis ``b_axis + 1``,
    absolute position ``p`` in slot ``p % C``.  ``pos`` (b,) int32 counts
    tokens written per row (the absolute cursor)."""

    k: jax.Array                      # (*lead, b, C, kvh, hd)
    v: jax.Array
    pos: jax.Array                    # (b,) int32
    b_axis: int

    @classmethod
    def create(cls, lead, batch, capacity, kv_heads, head_dim, dtype, *,
               b_axis=None):
        shape = tuple(lead) + (batch, capacity, kv_heads, head_dim)
        b_axis = len(lead) if b_axis is None else b_axis
        return cls(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   pos=jnp.zeros((batch,), jnp.int32), b_axis=b_axis)

    @property
    def capacity(self) -> int:
        return self.k.shape[self.b_axis + 1]

    def replace(self, **kw) -> "RingKV":
        return dataclasses.replace(self, **kw)

    def slot(self, i: int) -> "RingKV":
        return self.replace(k=_slice_axis(self.k, self.b_axis, i),
                            v=_slice_axis(self.v, self.b_axis, i),
                            pos=_slice_axis(self.pos, 0, i))

    def set_slot(self, i: int, sub: "RingKV") -> "RingKV":
        return self.replace(k=_set_axis(self.k, self.b_axis, i, sub.k),
                            v=_set_axis(self.v, self.b_axis, i, sub.v),
                            pos=self.pos.at[i:i + 1].set(sub.pos))

    def reset_row(self, i: int) -> "RingKV":
        return self.replace(pos=self.pos.at[i].set(0))

    def lengths(self) -> jax.Array:
        return jnp.minimum(self.pos, self.capacity)

    # -- the per-row wrap-aware mapping into the flash kernel's SMEM lanes --
    def attend_lens(self, pos) -> jax.Array:
        """``kv_len`` vector for a decode at absolute positions ``pos``
        (b,): ``min(pos + 1, C)`` slots are live.  With ``q_offset = pos``
        and causal masking the kernel attends exactly those — an unwrapped
        row's contiguous prefix, or (wrapped) the whole ring, every slot of
        which is inside the window since ``C <= window``."""
        return jnp.minimum(jnp.asarray(pos, jnp.int32) + 1, self.capacity)

    def slot_positions(self, pos) -> jax.Array:
        """True position held by each slot, per row: slot ``j`` holds
        ``pos - ((pos - j) mod C)``; never-written slots surface a huge
        positive position so causal masking kills them.  (b, C) int32 —
        the jnp oracle's key positions."""
        c = self.capacity
        pos = jnp.asarray(pos, jnp.int32).reshape(-1, 1)
        idx = jnp.arange(c, dtype=jnp.int32)[None, :]
        ring_pos = pos - ((pos - idx) % c)
        return jnp.where(ring_pos >= 0, ring_pos, jnp.int32(1 << 30))


_register(RingKV, ("k", "v", "pos"), ("b_axis",))


@dataclass(frozen=True)
class CrossKV:
    """Cross-attention k/v, written at a request's first prefill chunk and
    frozen for its lifetime.  Position-free."""

    k: jax.Array                      # (*lead, b, E, kvh, hd)
    v: jax.Array
    b_axis: int

    @classmethod
    def create(cls, lead, batch, enc, kv_heads, head_dim, dtype, *,
               b_axis=None):
        shape = tuple(lead) + (batch, enc, kv_heads, head_dim)
        b_axis = len(lead) if b_axis is None else b_axis
        return cls(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   b_axis=b_axis)

    def replace(self, **kw) -> "CrossKV":
        return dataclasses.replace(self, **kw)

    def slot(self, i: int) -> "CrossKV":
        return self.replace(k=_slice_axis(self.k, self.b_axis, i),
                            v=_slice_axis(self.v, self.b_axis, i))

    def set_slot(self, i: int, sub: "CrossKV") -> "CrossKV":
        return self.replace(k=_set_axis(self.k, self.b_axis, i, sub.k),
                            v=_set_axis(self.v, self.b_axis, i, sub.v))

    def reset_row(self, i: int) -> "CrossKV":
        return self  # overwritten wholesale at the next first chunk

    def lengths(self):
        return None


_register(CrossKV, ("k", "v"), ("b_axis",))


@dataclass(frozen=True)
class StateCarry:
    """Recurrent per-row state: a dict of arrays, every one with batch at
    axis 1 (layer-stacked in front).  ``valid`` (b,) bool marks rows whose
    carried state belongs to a live decode — a reused slot resets its row
    independently of its neighbours."""

    states: dict
    valid: jax.Array                  # (b,) bool

    @classmethod
    def create(cls, states: dict):
        batch = next(iter(states.values())).shape[1]
        return cls(states=dict(states),
                   valid=jnp.ones((batch,), bool))

    def replace(self, **kw) -> "StateCarry":
        return dataclasses.replace(self, **kw)

    def slot(self, i: int) -> "StateCarry":
        return StateCarry(
            states={k: _slice_axis(a, 1, i) for k, a in self.states.items()},
            valid=_slice_axis(self.valid, 0, i))

    def set_slot(self, i: int, sub: "StateCarry") -> "StateCarry":
        return StateCarry(
            states={k: _set_axis(a, 1, i, sub.states[k])
                    for k, a in self.states.items()},
            valid=self.valid.at[i:i + 1].set(sub.valid))

    def reset_row(self, i: int) -> "StateCarry":
        return StateCarry(
            states={k: _set_axis(a, 1, i, jnp.zeros_like(_slice_axis(a, 1, i)))
                    for k, a in self.states.items()},
            valid=self.valid.at[i].set(False))

    def set_row_valid(self, i: int, flag: bool) -> "StateCarry":
        return self.replace(valid=self.valid.at[i].set(bool(flag)))

    def lengths(self):
        return None


_register(StateCarry, ("states", "valid"))

_LAYOUTS = (LinearKV, RingKV, CrossKV, StateCarry)


# ---------------------------------------------------------------------------
# composite helpers: a cache is a layout, or a dict/tuple of caches
# ---------------------------------------------------------------------------

def _map_layouts(cache, fn, *others):
    """``fn`` over every layout of ``cache``, given the entries of
    ``others`` (trees of the composite's structure) at the same place."""
    if isinstance(cache, _LAYOUTS):
        return fn(cache, *others)
    if isinstance(cache, dict):
        return {k: _map_layouts(v, fn, *(o[k] for o in others))
                for k, v in cache.items()}
    if isinstance(cache, (tuple, list)):
        return type(cache)(_map_layouts(v, fn, *os)
                           for v, *os in zip(cache, *others))
    raise TypeError(f"not a DecodeCache composite: {type(cache)!r}")


def slot(cache, i: int):
    """The b=1 slice of every layout for engine slot ``i``."""
    return _map_layouts(cache, lambda lo: lo.slot(i))


def set_slot(cache, i: int, sub):
    """Write a b=1 sub-cache back into slot ``i`` of every layout."""
    return _map_layouts(cache, lambda lo, s: lo.set_slot(i, s), sub)


def reset_row(cache, i: int):
    """Row ``i`` leaves its request: cursors to zero, recurrent state
    zeroed and invalidated.  The engine calls this at admission so a reused
    slot never sees its predecessor's state."""
    return _map_layouts(cache, lambda lo: lo.reset_row(i))


def set_row_valid(cache, i: int, flag: bool):
    """Flip row ``i``'s recurrent-state validity (StateCarry layouts only;
    positional layouts are already row-exact via their cursors)."""
    return _map_layouts(
        cache,
        lambda lo: lo.set_row_valid(i, flag) if isinstance(lo, StateCarry)
        else lo)


def snapshot_row(cache, i: int):
    """Host-staged copy of slot ``i`` across every layout: the b=1 pytree
    slice of the whole composite with numpy leaves, so the snapshot costs
    no device memory and survives the engine's donated-buffer launches.
    This copies the whole row, every ``LinearKV`` position up to the
    capacity included.  The serving engine fills the same format through
    :class:`RowCopier`, which moves only the positions written since the
    row's previous snapshot.  Taken on a token-count cadence by the
    engine, it is the resume point for BOTH fault recovery (a poisoned
    row) and pressure eviction — restore plus a short greedy token replay
    instead of whole-residency recompute.  Restore with
    :func:`restore_row`, into the same or a DIFFERENT slot (row slices
    carry no slot identity)."""
    return jax.device_get(slot(cache, i))


def _row_head(cache, i):
    """Row ``i`` of everything a :class:`RowCopier` copies whole: every
    layout but the ``LinearKV`` slabs, and of those their cursor and
    scales."""
    return _map_layouts(
        slot(cache, i),
        lambda lo: lo.replace(k=None, v=None) if isinstance(lo, LinearKV)
        else lo)


def _row_block(cache, i, start, *, width: int):
    """Positions ``[start, start + width)`` of row ``i`` of every
    ``LinearKV`` slab (a ``(k, v)`` pair in its place; None elsewhere)."""
    return _map_layouts(
        cache, lambda lo: lo.block(i, start, width)
        if isinstance(lo, LinearKV) else None)


def _new_slabs(lo):
    """Zeroed host k and v buffers for one row of a ``LinearKV`` (None for
    any other layout)."""
    if not isinstance(lo, LinearKV):
        return None
    shape = list(lo.k.shape)
    shape[lo.b_axis] = 1
    return np.zeros(shape, lo.k.dtype), np.zeros(shape, lo.v.dtype)


class RowCopier:
    """Host snapshots of cache rows that move only what changed.

    A ``LinearKV`` row is append-only while it holds one request: writes
    land at the row's cursor, and a parked row writes at or beyond its
    own.  So the positions below a row's previous snapshot are on the host
    already, unchanged, and :meth:`copy` moves ``[since, upto)`` alone, in
    place into the previous snapshot's slab buffers; a row's first
    snapshot moves ``[0, upto)``.  Every other layout (``RingKV`` wraps,
    ``CrossKV``, ``StateCarry``) and each ``LinearKV``'s cursor and scales
    cross whole.  The result has :func:`snapshot_row`'s format and
    equals it on every position below the cursor.

    The positions cross in blocks of a few fixed ``widths``: the widest
    while it fits, then the narrowest to the end.  Each block, and the
    whole-copied part, is one compiled program with the slot and the
    start traced, per cache placement (the shardings of its leaves);
    :meth:`compile` builds them ahead of use, and a placement never seen
    compiles on first use."""

    def __init__(self, widths):
        self.widths = tuple(sorted({int(w) for w in widths if w > 0},
                                   reverse=True))
        if not self.widths:
            raise ValueError(f"RowCopier needs a positive width: {widths}")
        self._programs: dict = {}

    @staticmethod
    def _placement(cache) -> tuple:
        return tuple(x.sharding for x in jax.tree.leaves(cache))

    def _program(self, width, placement, cache):
        """The compiled copy of one block of ``width`` (None: the
        whole-copied part) for caches placed as ``placement``."""
        key = (width, placement)
        prog = self._programs.get(key)
        if prog is None:
            scalar = jax.ShapeDtypeStruct((), jnp.int32)
            if width is None:
                prog = jax.jit(_row_head).lower(cache, scalar).compile()
            else:
                prog = jax.jit(_row_block, static_argnames="width").lower(
                    cache, scalar, scalar, width=width).compile()
            self._programs[key] = prog
        return prog

    def compile(self, cache) -> None:
        """Build every program for caches placed as ``cache`` is (its
        leaves may be ``jax.ShapeDtypeStruct`` with a sharding)."""
        placement = self._placement(cache)
        for width in (None,) + self._widths(cache):
            self._program(width, placement, cache)

    def _widths(self, cache) -> tuple:
        """The block widths ``cache`` needs: none without a ``LinearKV``."""
        found = []
        _map_layouts(cache, lambda lo: found.append(isinstance(lo, LinearKV)))
        return self.widths if any(found) else ()

    @staticmethod
    def _blocks(widths: tuple, since: int, upto: int) -> list:
        """``(width, start)`` of the blocks that cover ``[since, upto)``:
        the widest of ``widths`` while it fits, then the narrowest."""
        blocks, at = [], since
        for w in widths:
            while upto - at >= w:
                blocks.append((w, at))
                at += w
        while widths and at < upto:
            blocks.append((widths[-1], at))
            at += widths[-1]
        return blocks

    def copy(self, cache, i: int, upto: int, base=None, since: int = 0):
        """Row ``i`` as a :func:`snapshot_row` at cursor ``upto``.  With
        ``base``, the row's snapshot at cursor ``since``, only ``LinearKV``
        positions ``[since, upto)`` cross, written into ``base``'s slab
        buffers (``base`` stays a valid snapshot at ``since`` throughout:
        nothing below ``since`` changes); without, ``[0, upto)`` cross
        into new buffers.  Every program is dispatched before one read of
        them all.  Returns the row and the bytes moved to the host."""
        placement = self._placement(cache)
        slot_i = np.int32(i)
        blocks = self._blocks(self._widths(cache),
                              since if base is not None else 0, upto)
        outs = [self._program(None, placement, cache)(cache, slot_i)]
        outs += [self._program(w, placement, cache)(cache, slot_i,
                                                     np.int32(start))
                 for w, start in blocks]
        host = jax.device_get(outs)
        moved = sum(x.nbytes for x in jax.tree.leaves(host))

        slabs = (_map_layouts(cache, _new_slabs) if base is None else
                 _map_layouts(cache, lambda lo, prev: (prev.k, prev.v)
                              if isinstance(lo, LinearKV) else None, base))

        def write(lo, bufs, *got):
            if isinstance(lo, LinearKV):
                for (w, start), kv in zip(blocks, got):
                    seq = (slice(None),) * (lo.b_axis + 1) + (
                        lo.block_range(start, w),)
                    for buf, blk in zip(bufs, kv):
                        buf[seq] = blk

        _map_layouts(cache, write, slabs, *host[1:])
        row = _map_layouts(
            cache, lambda lo, head, bufs: head.replace(k=bufs[0], v=bufs[1])
            if isinstance(lo, LinearKV) else head, host[0], slabs)
        return row, moved


def restore_row(cache, i: int, snap):
    """Write a :func:`snapshot_row` back into slot ``i``: slabs, positional
    cursors, int8 scales, frozen cross-KV, recurrent state and its validity
    all land, so the row resumes exactly at its snapshot point."""
    return set_slot(cache, i, snap)


def snapshot_compatible(cache, snap) -> None:
    """The cross-replica portability gate: validate that a host-staged
    :func:`snapshot_row` can restore into ``cache`` — same composite
    structure, every leaf matching the cache's own b=1 row slice in shape
    and dtype.  Row slices carry no slot or replica identity, so a
    snapshot taken on one replica restores into ANY replica built from the
    same serving config; a mismatch (different ``max_len``, window,
    quantization, or family) must fail loudly here, not corrupt a row.
    Raises ``ValueError`` naming the first mismatch; cost is abstract-only
    (``eval_shape`` — no device work)."""
    ref = jax.eval_shape(lambda: slot(cache, 0))
    ref_leaves, ref_def = jax.tree_util.tree_flatten(ref)
    snap_leaves, snap_def = jax.tree_util.tree_flatten(snap)
    if ref_def != snap_def:
        raise ValueError(
            f"snapshot layout mismatch: cache rows are {ref_def}, "
            f"snapshot is {snap_def}")
    for r, s in zip(ref_leaves, snap_leaves):
        if tuple(r.shape) != tuple(np.shape(s)):
            raise ValueError(
                f"snapshot row shape mismatch: cache row leaf {r.shape} "
                f"vs snapshot leaf {np.shape(s)}")
        if jnp.dtype(r.dtype) != jnp.dtype(np.asarray(s).dtype):
            raise ValueError(
                f"snapshot row dtype mismatch: cache row leaf {r.dtype} "
                f"vs snapshot leaf {np.asarray(s).dtype}")


def lengths(cache):
    """Per-row context depth: the elementwise max over every positional
    layout's lengths, or None if the cache is position-free (pure state
    carry)."""
    found = []
    _map_layouts(cache, lambda lo: found.append(lo.lengths()) or lo)
    vecs = [x for x in found if x is not None]
    if not vecs:
        return None
    out = vecs[0]
    for x in vecs[1:]:
        out = jnp.maximum(out, x)
    return out


# ---------------------------------------------------------------------------
# mutation helpers — the only sanctioned cache writes
# ---------------------------------------------------------------------------

def linear_write(slab, new, write_at):
    """Write ``new`` (b, s, kvh, hd) into a linear slab at sequence offset
    ``write_at`` — a scalar (lockstep: every row at the same depth) or a
    (b,) vector (continuous batching: each slot at its own depth, one
    vmapped per-row dynamic slice)."""
    if jnp.ndim(write_at) == 0:
        return jax.lax.dynamic_update_slice_in_dim(slab, new, write_at,
                                                   axis=1)
    return jax.vmap(
        lambda c, n, w: jax.lax.dynamic_update_slice_in_dim(c, n, w, axis=0)
    )(slab, new, write_at)


def ring_write(slab, new, write_at):
    """Write ``new`` (b, s, kvh, hd) into a ring slab (b, C, kvh, hd) at
    absolute offset ``write_at`` (scalar or (b,)): position ``p`` lands in
    slot ``p % C``.  When ``s >= C`` only the last ``C`` tokens survive
    (unique slots — no scatter-order hazard)."""
    b, s = new.shape[:2]
    c = slab.shape[1]
    wa = jnp.broadcast_to(jnp.asarray(write_at, jnp.int32), (b,))
    if s >= c:
        new = new[:, s - c:]
        wa = wa + (s - c)
        s = c
    idx = (wa[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]) % c
    return jax.vmap(lambda sl, n, ix: sl.at[ix].set(n))(slab, new, idx)


def masked_rows(mask, new, old, axis: int = 0):
    """Per-row select ``where(mask, new, old)`` with ``mask`` (b,) aligned
    to the batch ``axis`` and broadcast over every other dim — the
    row-isolation update discipline (decode: mask = valid; prefill chunk:
    mask = chunk_lens > 0; frozen CrossKV slabs: mask = first-chunk rows)."""
    mask = jnp.asarray(mask)
    shape = [1] * new.ndim
    shape[axis] = mask.shape[0]
    return jnp.where(mask.reshape(shape), new, old)


def conv_tail(xp, lens, width: int):
    """Per-row causal-conv state after consuming ``lens`` valid tokens of a
    padded chunk.  ``xp`` (b, s + width, dim) is the conv input with the
    previous state prepended; row ``r``'s new state is
    ``xp[r, lens[r] : lens[r] + width]`` — ``lens = 0`` returns the old
    state untouched, ``lens = s`` the true tail."""
    xp_len = xp.shape[1]
    lens = jnp.broadcast_to(jnp.asarray(lens, jnp.int32), (xp.shape[0],))
    lens = jnp.clip(lens, 0, xp_len - width)
    return jax.vmap(
        lambda row, l: jax.lax.dynamic_slice_in_dim(row, l, width, axis=0)
    )(xp, lens)


def pick_last(x, lens):
    """Each row's features at its last valid token: ``x`` (b, s, d),
    ``lens`` (b,) valid counts (None = the full chunk) -> (b, d)."""
    if lens is None:
        return x[:, -1]
    row = jnp.clip(jnp.asarray(lens, jnp.int32) - 1, 0, x.shape[1] - 1)
    return jnp.take_along_axis(x, row[:, None, None], axis=1)[:, 0]


def token_mask(lens, s: int):
    """(b, s) bool valid-token mask from per-row counts; None = all valid
    (the lockstep full-sequence path takes no masking at all)."""
    if lens is None:
        return None
    lens = jnp.asarray(lens, jnp.int32)
    return jnp.arange(s, dtype=jnp.int32)[None, :] < lens[:, None]
