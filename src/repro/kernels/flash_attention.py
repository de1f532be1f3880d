"""Flash attention Pallas kernel — the BP online-softmax reduce as a TPU
kernel (the kernel twin of ``repro.models.common.attention_blockwise``).

Grid: (batch*heads * nq, nk) with the KV loop innermost; running (m, l, acc)
live in VMEM scratch (the BP up-pass combine state); causal/sliding-window
masking from block offsets via iota.  The flattened outer (bh, nq) grid is
decoded through ``repro.kernels.morton.grid_decode`` — Morton (BI) order
when square power-of-two, so consecutive outer steps revisit the same KV
panels (the §3.2 block-sharing argument applied to the schedule); row-major
fallback otherwise.  The KV sweep for one (b, q) pair always stays
contiguous (the scratch accumulator requires it).

Cached decode (serving) is covered by two kwargs:

``q_offset``
    Absolute position of query row 0 (``q`` row ``i`` sits at position
    ``q_offset + i``; keys sit at positions ``0..sk-1``).  May be a traced
    scalar — the decode loop's ``pos`` — passed to the kernel through SMEM,
    so per-step offsets never recompile.

``kv_len``
    Number of valid KV slots; keys at or beyond it are masked.  A *static*
    ``kv_len`` shrinks the KV grid itself (the planner-aware grid: only
    ``ceil(kv_len / kv_block)`` blocks are ever visited); a traced one keeps
    the full grid and skips dead blocks with ``pl.when`` (no recompiles
    across decode steps).

Both kwargs also accept a PER-ROW vector of shape ``(rows,)`` where ``rows``
divides the folded batch-head count (``rows`` = the batch under the
batch-major head fold) — the continuous-batching contract: each batch lane
carries its own decode position and its own valid cache prefix, so cache
slots at different depths coexist in one kernel launch.  The vectors live
in SMEM; each grid step indexes its lane's scalars (``r = bh // hpb``), and
a traced vector keeps the no-recompile property across decode steps of
varying per-row lengths.  A *concrete* (numpy) vector still shrinks the KV
grid to ``ceil(max(kv_len) / kv_block)`` blocks; shorter lanes skip their
dead blocks with ``pl.when``.  A lane with ``kv_len == 0`` (nothing valid
yet) emits zeros through the ``l_safe`` guard.

A query row with every key masked (possible when ``window > 0`` and
``q_offset`` outruns ``kv_len``) returns zeros — masked probabilities are
explicitly zeroed so the ``l`` accumulator stays 0 and the ``l_safe`` guard
emits 0, matching ``ref.flash_attention_ref``.

The kernel carries a custom VJP (registered per static config): the
recomputation-style flash backward — forward also emits the per-row LSE,
backward recomputes P per block from (q, k, lse) and produces dq (KV-sweep
grid) and dk/dv (q-sweep grid) without ever materializing an O(sq*sk)
tensor.  A pallas-resolving execution policy therefore no longer needs to
route attention around the kernel under autodiff.

GQA is kernel-native: callers pass K/V at their *native* head count and the
kv ``index_map`` routes each query head's grid step straight into its group's
KV row (``bh -> (bh // h) * kvh + (bh % h) // n_rep`` under the batch-major
head fold) — the cache-sized ``repeat_kv`` materialization the adapter used
to pay per decode step is gone; every head in a group re-reads the *same*
blocks, which is exactly the paper's O(1)-block-sharing discipline.  The
caller declares its per-batch query head count via ``n_heads`` whenever
``k.shape[0] < q.shape[0]``.  The backward keeps the no-copy contract: dq
runs on the forward grid with the same kv index map, and dk/dv extend the
transposed KV-outer grid's inner axis to ``n_rep * nq`` — each KV tile's
scratch accumulates the contributions of all ``n_rep`` query heads in its
group before emitting, so the group sum happens in VMEM, never through an
O(n_rep)-sized intermediate.

Quantized KV (serving): int8 ``k``/``v`` with per-(batch, kv-head) f32
scales (``k_scale``/``v_scale``, shape ``(kbh,)``) dequantize *inside* the
kernel block load — the cache streams at 1/4 the f32 block traffic and the
f32 copy never exists outside VMEM.  The quantized path is forward-only
(decode never differentiates; int8 carries no tangent).

``q_block=None`` / ``kv_block=None`` (the defaults) plan the blocks from
the queried device via ``repro.kernels.planner`` (per-dtype envelopes: an
int8 KV stream budgets a deeper panel); ragged sequence lengths snap each
block down to the largest divisor of its axis instead of asserting, and a
degenerate snap (prime-ish lengths) falls back to the jnp oracle — or
raises, under a ``strict_tiles`` policy.

The per-row softmax statistics (m, l, LSE, and the backward's delta) are
``(rows, 1)`` columns, never 1-D vectors: Mosaic tiles the last two dims of
every block by (8, 128), and a column keeps each block's second-minor dim a
multiple of 8 or the whole axis.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.morton import grid_decode

NEG_INF = -1e30


def _kv_index(b_, *, h: int, kvh: int, n_rep: int):
    """Native-KV-head GQA index: query batch-head ``b_`` (batch-major fold,
    head = kv_head * n_rep + rep) -> its group's KV batch-head row.  Plain
    integer arithmetic, works on traced grid indices."""
    if n_rep == 1:
        return b_
    return (b_ // h) * kvh + (b_ % h) // n_rep


def _mask(qoff, kvlen, qi, kb, *, causal, window, q_block, kv_block,
          full_len):
    """(q_block, kv_block) validity mask from block coordinates and the SMEM
    scalars; shared by the forward and both backward kernels so the three
    recomputations of P agree bit-for-bit.  ``full_len`` (static: kv_len
    covers the whole KV axis) drops the validity term; with no causal/window
    masking either, returns None — the caller skips masking entirely, so
    plain self-attention pays nothing for the decode machinery."""
    if full_len and not causal and window <= 0:
        return None
    q_pos = qoff + qi * q_block + jax.lax.broadcasted_iota(
        jnp.int32, (q_block, kv_block), 0)
    k_pos = kb * kv_block + jax.lax.broadcasted_iota(
        jnp.int32, (q_block, kv_block), 1)
    ok = None if full_len else (k_pos < kvlen)
    if causal:
        c = k_pos <= q_pos
        ok = c if ok is None else ok & c
    if window > 0:
        w = k_pos > q_pos - window
        ok = w if ok is None else ok & w
    return ok


def _run_kv_block(body, kb, kvlen, *, kv_block, full_len):
    """Run ``body`` for one KV block, skipping blocks past ``kv_len`` via
    ``pl.when`` — unless the static config says every block is live."""
    if full_len:
        body()
    else:
        pl.when(kb * kv_block < kvlen)(body)


def _flash_kernel(qoff_ref, kvlen_ref, *refs, scale: float, causal: bool,
                  window: int, q_block: int, kv_block: int, nk: int,
                  full_len: bool, decode, quantized: bool, h: int, kvh: int,
                  n_rep: int, hpb: int):
    if quantized:
        (kscale_ref, vscale_ref, q_ref, k_ref, v_ref,
         o_ref, lse_ref, m_ref, l_ref, acc_ref) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref = refs
        kscale_ref = vscale_ref = None
    kb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    b_, qi = decode(pl.program_id(0))
    # per-row decode state: lane r = b_ // hpb (hpb = batch-heads per row;
    # rows == 1 makes this the old shared-scalar read)
    qoff, kvlen = qoff_ref[b_ // hpb], kvlen_ref[b_ // hpb]

    def _body():
        q = q_ref[0].astype(jnp.float32)  # (q_block, hd)
        k = k_ref[0].astype(jnp.float32)  # (kv_block, hd)
        v = v_ref[0].astype(jnp.float32)
        if quantized:
            # per-(batch, kv-head) dequant at the block load: the int8 cache
            # is the only thing that ever crossed slow memory
            kvb = _kv_index(b_, h=h, kvh=kvh, n_rep=n_rep)
            k = k * kscale_ref[kvb]
            v = v * vscale_ref[kvb]

        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        ok = _mask(qoff, kvlen, qi, kb, causal=causal, window=window,
                   q_block=q_block, kv_block=kv_block, full_len=full_len)
        if ok is not None:
            s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[...]  # (q_block, 1): row stats stay 2-D for Mosaic
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if ok is not None:
            # explicit zero at masked slots: when a row is fully masked m_new
            # is still NEG_INF and exp(s - m_new) would be 1, silently
            # averaging v
            p = jnp.where(ok, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    _run_kv_block(_body, kb, kvlen, kv_block=kv_block, full_len=full_len)

    @pl.when(kb == nk - 1)
    def _emit():
        l_safe = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = m_ref[...] + jnp.log(l_safe)


def _probs_from_lse(s, ok, lse):
    """exp(s - lse) = softmax probs (lse folds the l normalizer; a
    (q_block, 1) column); the explicit zero guards fully-masked rows where
    lse ~ NEG_INF."""
    p = jnp.exp(s - lse)
    return p if ok is None else jnp.where(ok, p, 0.0)


def _bwd_dq_kernel(qoff_ref, kvlen_ref, q_ref, k_ref, v_ref, g_ref, lse_ref,
                   delta_ref, dq_ref, dq_acc, *, scale: float, causal: bool,
                   window: int, q_block: int, kv_block: int, nk: int,
                   full_len: bool, decode, hpb: int):
    """dq = sum over KV blocks of (P * (dO K^T... ) ) — same grid shape and
    schedule as the forward, accumulating dq in scratch.  GQA needs no body
    change here: the kv index map hands each query head its group's native
    KV blocks."""
    kb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    b_, qi = decode(pl.program_id(0))
    qoff, kvlen = qoff_ref[b_ // hpb], kvlen_ref[b_ // hpb]

    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        g = g_ref[0].astype(jnp.float32)
        lse = lse_ref[0]      # (q_block, 1) f32
        delta = delta_ref[0]  # (q_block, 1) f32 rowsum(dO * O)

        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        ok = _mask(qoff, kvlen, qi, kb, causal=causal, window=window,
                   q_block=q_block, kv_block=kv_block, full_len=full_len)
        if ok is not None:
            s = jnp.where(ok, s, NEG_INF)
        p = _probs_from_lse(s, ok, lse)
        dp = jnp.dot(g, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_acc[...] += jnp.dot(ds, k, preferred_element_type=jnp.float32)

    _run_kv_block(_body, kb, kvlen, kv_block=kv_block, full_len=full_len)

    @pl.when(kb == nk - 1)
    def _emit():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(qoff_ref, kvlen_ref, q_ref, k_ref, v_ref, g_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float,
                    causal: bool, window: int, q_block: int, kv_block: int,
                    nq: int, n_rep: int, full_len: bool, decode, khpb: int):
    """dk/dv: the transposed sweep — outer grid over (kbh, nk) *native* KV
    tiles, inner loop over ``n_rep * nq`` (every q block of every query head
    in this KV head's group), accumulating (kv_block, hd) dk/dv in scratch —
    the GQA group sum lives in the accumulator, no repeated KV ever exists.
    KV blocks beyond ``kv_len`` (and, under causal masking, q blocks entirely
    before the KV block) skip the matmuls but still emit their zeros."""
    j = pl.program_id(1)
    qi = j % nq if n_rep > 1 else j  # inner axis = (rep, qi), rep-major

    @pl.when(j == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    b_, kb = decode(pl.program_id(0))
    # lane index through the KV batch-head fold (khpb = kv batch-heads/row)
    qoff, kvlen = qoff_ref[b_ // khpb], kvlen_ref[b_ // khpb]

    live = None if full_len else (kb * kv_block < kvlen)
    if causal:
        # max q position in this q block >= min k position in this kv block
        c = qoff + (qi + 1) * q_block - 1 >= kb * kv_block
        live = c if live is None else live & c

    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        g = g_ref[0].astype(jnp.float32)
        lse = lse_ref[0]
        delta = delta_ref[0]

        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        ok = _mask(qoff, kvlen, qi, kb, causal=causal, window=window,
                   q_block=q_block, kv_block=kv_block, full_len=full_len)
        if ok is not None:
            s = jnp.where(ok, s, NEG_INF)
        p = _probs_from_lse(s, ok, lse)
        dv_acc[...] += jnp.dot(p.T, g, preferred_element_type=jnp.float32)
        dp = jnp.dot(g, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_acc[...] += jnp.dot(ds.T, q, preferred_element_type=jnp.float32)

    if live is None:
        _body()
    else:
        pl.when(live)(_body)

    @pl.when(j == n_rep * nq - 1)
    def _emit():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _gqa_geometry(q, k, n_heads: Optional[int]):
    """(h, kvh, n_rep) from the folded shapes and the caller's declared
    per-batch query head count."""
    bh, kbh = q.shape[0], k.shape[0]
    if bh == kbh:
        return bh, kbh, 1
    if n_heads is None:
        raise ValueError(
            f"native-GQA flash_attention: k has {kbh} batch-heads vs q's "
            f"{bh}; pass n_heads (query heads per batch) so the kv index "
            "map can decompose the batch-head fold")
    if bh % kbh != 0:
        raise ValueError(f"q batch-heads {bh} not a multiple of kv "
                         f"batch-heads {kbh}")
    n_rep = bh // kbh
    if n_heads % n_rep != 0 or bh % n_heads != 0:
        raise ValueError(f"n_heads={n_heads} incompatible with q/kv "
                         f"batch-heads ({bh}, {kbh})")
    return n_heads, n_heads // n_rep, n_rep


def _fwd_call(q, k, v, qoff, kvlen, kscale, vscale, *, causal, window,
              q_block, kv_block, nk_run, full_len, n_heads, rows, interpret):
    """Forward pallas_call: returns (out, lse)."""
    bh, sq, hd = q.shape
    nq = sq // q_block
    scale = 1.0 / math.sqrt(hd)
    h, kvh, n_rep = _gqa_geometry(q, k, n_heads)
    hpb = bh // rows  # batch-heads per decode lane (rows == 1: one lane)
    quantized = kscale is not None
    # BI order over the flattened (bh, nq) outer grid; the KV dim stays the
    # trailing (contiguous) grid axis so the scratch combine is well-defined.
    decode = grid_decode(bh, nq)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    def q_map(g, j):
        b, i = decode(g)
        return (b, i, 0)

    def kv_map(g, j):
        b, _ = decode(g)
        return (_kv_index(b, h=h, kvh=kvh, n_rep=n_rep), j, 0)

    def row_map(g, j):
        b, i = decode(g)
        return (b, i, 0)

    in_specs = [smem, smem]
    operands = [qoff, kvlen]
    if quantized:
        in_specs += [smem, smem]
        operands += [kscale, vscale]
    in_specs += [pl.BlockSpec((1, q_block, hd), q_map),
                 pl.BlockSpec((1, kv_block, hd), kv_map),
                 pl.BlockSpec((1, kv_block, hd), kv_map)]
    operands += [q, k, v]

    return pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          window=window, q_block=q_block, kv_block=kv_block,
                          nk=nk_run, full_len=full_len, decode=decode,
                          quantized=quantized, h=h, kvh=kvh, n_rep=n_rep,
                          hpb=hpb),
        grid=(bh * nq, nk_run),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, q_block, hd), q_map),
                   pl.BlockSpec((1, q_block, 1), row_map)],
        out_shape=[jax.ShapeDtypeStruct((bh, sq, hd), q.dtype),
                   jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((q_block, 1), jnp.float32),
            pltpu.VMEM((q_block, 1), jnp.float32),
            pltpu.VMEM((q_block, hd), jnp.float32),
        ],
        interpret=interpret,
    )(*operands)


def _bwd_call(q, k, v, qoff, kvlen, out, lse, g, *, causal, window, q_block,
              kv_block, nk_run, full_len, n_heads, rows, interpret):
    """Backward pallas_calls: dq over the forward's (q-outer, kv-inner) grid,
    dk/dv over the transposed (kv-outer, (rep, q)-inner) grid at the native
    KV head count."""
    bh, sq, hd = q.shape
    sk = k.shape[1]
    kbh = k.shape[0]
    nq = sq // q_block
    nk_full = sk // kv_block
    scale = 1.0 / math.sqrt(hd)
    h, kvh, n_rep = _gqa_geometry(q, k, n_heads)
    hpb = bh // rows
    khpb = kbh // rows  # kv batch-heads per decode lane
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1,
                    keepdims=True)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    dec_q = grid_decode(bh, nq)

    def q_map(g_, j):
        b, i = dec_q(g_)
        return (b, i, 0)

    def kv_map(g_, j):
        b, _ = dec_q(g_)
        return (_kv_index(b, h=h, kvh=kvh, n_rep=n_rep), j, 0)

    def row_map(g_, j):
        b, i = dec_q(g_)
        return (b, i, 0)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          window=window, q_block=q_block, kv_block=kv_block,
                          nk=nk_run, full_len=full_len, decode=dec_q,
                          hpb=hpb),
        grid=(bh * nq, nk_run),
        in_specs=[smem, smem,
                  pl.BlockSpec((1, q_block, hd), q_map),
                  pl.BlockSpec((1, kv_block, hd), kv_map),
                  pl.BlockSpec((1, kv_block, hd), kv_map),
                  pl.BlockSpec((1, q_block, hd), q_map),
                  pl.BlockSpec((1, q_block, 1), row_map),
                  pl.BlockSpec((1, q_block, 1), row_map)],
        out_specs=pl.BlockSpec((1, q_block, hd), q_map),
        out_shape=jax.ShapeDtypeStruct((bh, sq, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((q_block, hd), jnp.float32)],
        interpret=interpret,
    )(qoff, kvlen, q, k, v, g, lse, delta)

    # transposed grid at the NATIVE kv head count: the full nk (not the
    # shrunk run) so every dk/dv block is written — dead blocks emit the
    # zeros their masked keys earn.  The inner axis covers (rep, q block):
    # each KV tile accumulates its whole group's contributions in scratch
    dec_kv = grid_decode(kbh, nk_full)

    def _qbh(b, j):
        # query batch-head for kv batch-head ``b`` and inner index ``j``
        if n_rep == 1:
            return b
        return (b // kvh) * h + (b % kvh) * n_rep + j // nq

    def kv_map_t(g_, j):
        b, i = dec_kv(g_)
        return (b, i, 0)

    def q_map_t(g_, j):
        b, _ = dec_kv(g_)
        return (_qbh(b, j), j % nq if n_rep > 1 else j, 0)

    def row_map_t(g_, j):
        b, _ = dec_kv(g_)
        return (_qbh(b, j), j % nq if n_rep > 1 else j, 0)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          window=window, q_block=q_block, kv_block=kv_block,
                          nq=nq, n_rep=n_rep, full_len=full_len,
                          decode=dec_kv, khpb=khpb),
        grid=(kbh * nk_full, n_rep * nq),
        in_specs=[smem, smem,
                  pl.BlockSpec((1, q_block, hd), q_map_t),
                  pl.BlockSpec((1, kv_block, hd), kv_map_t),
                  pl.BlockSpec((1, kv_block, hd), kv_map_t),
                  pl.BlockSpec((1, q_block, hd), q_map_t),
                  pl.BlockSpec((1, q_block, 1), row_map_t),
                  pl.BlockSpec((1, q_block, 1), row_map_t)],
        out_specs=[pl.BlockSpec((1, kv_block, hd), kv_map_t),
                   pl.BlockSpec((1, kv_block, hd), kv_map_t)],
        out_shape=[jax.ShapeDtypeStruct((kbh, sk, hd), k.dtype),
                   jax.ShapeDtypeStruct((kbh, sk, hd), v.dtype)],
        scratch_shapes=[pltpu.VMEM((kv_block, hd), jnp.float32),
                        pltpu.VMEM((kv_block, hd), jnp.float32)],
        interpret=interpret,
    )(qoff, kvlen, q, k, v, g, lse, delta)
    return dq, dk, dv


@functools.lru_cache(maxsize=None)
def _flash_fn(causal: bool, window: int, q_block: int, kv_block: int,
              nk_run: int, full_len: bool, n_heads: Optional[int],
              rows: int, quantized: bool, interpret: bool):
    """custom-VJP flash attention for one static config, jitted so repeated
    eager calls (tests, benchmarks) reuse the lowered kernel.  The quantized
    (int8 KV + scales) variant is forward-only."""
    cfg = dict(causal=causal, window=window, q_block=q_block,
               kv_block=kv_block, nk_run=nk_run, full_len=full_len,
               n_heads=n_heads, rows=rows, interpret=interpret)

    if quantized:
        def fa_quant(q, k, v, qoff, kvlen, kscale, vscale):
            out, _ = _fwd_call(q, k, v, qoff, kvlen, kscale, vscale, **cfg)
            return out

        return jax.jit(fa_quant)

    @jax.custom_vjp
    def fa(q, k, v, qoff, kvlen):
        out, _ = _fwd_call(q, k, v, qoff, kvlen, None, None, **cfg)
        return out

    def fa_fwd(q, k, v, qoff, kvlen):
        out, lse = _fwd_call(q, k, v, qoff, kvlen, None, None, **cfg)
        return out, (q, k, v, qoff, kvlen, out, lse)

    def fa_bwd(res, g):
        q, k, v, qoff, kvlen, out, lse = res
        dq, dk, dv = _bwd_call(q, k, v, qoff, kvlen, out, lse, g, **cfg)
        return dq, dk, dv, None, None

    fa.defvjp(fa_fwd, fa_bwd)
    return jax.jit(fa)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    q_offset: Optional[Union[int, jax.Array]] = None,
                    kv_len: Optional[Union[int, jax.Array]] = None,
                    q_block: Optional[int] = None,
                    kv_block: Optional[int] = None,
                    n_heads: Optional[int] = None,
                    k_scale: Optional[jax.Array] = None,
                    v_scale: Optional[jax.Array] = None,
                    interpret: Optional[bool] = None) -> jax.Array:
    """q: (bh, sq, hd); k, v: (kbh, sk, hd) — heads pre-folded into batch
    (batch-major: bh = batch * heads + head).  Returns (bh, sq, hd).

    GQA is kernel-native: ``kbh`` may be ``bh / n_rep`` (K/V at their native
    head count) with ``n_heads`` declaring the per-batch query head count —
    the kv index map routes each query head's blocks to its group's KV row,
    and the backward group-sums dk/dv inside the transposed grid.  No
    caller-side repeat.

    ``q_offset`` places query row i at absolute position ``q_offset + i``
    (keys at ``0..sk-1``); ``kv_len`` masks keys at positions >= it.  Both
    accept traced scalars (decode loops never recompile); a static ``kv_len``
    additionally shrinks the KV grid to ``ceil(kv_len / kv_block)`` blocks.
    Both also accept per-row vectors of shape ``(rows,)`` with ``rows``
    dividing ``bh`` and ``kbh`` (the continuous-batching contract, see the
    module docstring): traced vectors never recompile across steps, concrete
    (list/numpy) ``kv_len`` vectors shrink the grid to the longest lane.
    ``k_scale``/``v_scale`` (f32 ``(kbh,)``, paired with an int8 ``k``/``v``)
    dequantize per KV batch-head inside the kernel; the quantized path is
    forward-only.  Otherwise differentiable w.r.t. q/k/v via the registered
    recomputation backward.
    """
    from repro.kernels import planner

    bh, sq, hd = q.shape
    sk = k.shape[1]
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be passed together")
    quantized = k_scale is not None
    if q_block is None or kv_block is None:
        plan = planner.plan_attention(sq, sk, hd, q.dtype, kv_dtype=k.dtype)
        q_block = q_block if q_block is not None else plan["q_block"]
        kv_block = kv_block if kv_block is not None else plan["kv_block"]
    # ragged lengths snap each block to the largest divisor of its axis (the
    # planner's own plans are divisor-exact; this covers explicit overrides)
    q_block = planner.divisor_tile(sq, min(int(q_block), sq))
    kv_block = planner.divisor_tile(sk, min(int(kv_block), sk))
    # a degenerate snap (prime/odd axis -> sub-sublane tile on a long dim)
    # would run a catastrophically fine grid; take the jnp oracle instead
    if (q_block < 8 <= sq) or (kv_block < 8 <= sk):
        from repro.kernels import policy, ref

        if policy.current().strict_tiles:
            raise ValueError(f"flash_attention: blocks ({q_block}, "
                             f"{kv_block}) degenerate for sq={sq}, sk={sk}; "
                             "strict tiles forbid the oracle fallback")

        return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                       q_offset=q_offset, kv_len=kv_len,
                                       n_heads=n_heads, k_scale=k_scale,
                                       v_scale=v_scale)
    _gqa_geometry(q, k, n_heads)  # validate early, outside the jit
    nk_full = sk // kv_block

    def _veclen(x):
        if x is None or (isinstance(x, (int, np.integer))
                         and not isinstance(x, bool)):
            return 1
        shp = jnp.shape(x)
        if len(shp) > 1:
            raise ValueError(f"q_offset/kv_len must be scalar or 1-D, got "
                             f"shape {shp}")
        return int(shp[0]) if shp else 1

    def _concrete(x):
        """Host-known values as a numpy vector, else None (traced)."""
        if isinstance(x, (int, np.integer)) and not isinstance(x, bool):
            return np.asarray([int(x)], np.int64)
        if isinstance(x, (list, tuple, np.ndarray)):
            return np.asarray(x, np.int64).reshape(-1)
        return None

    # per-row lanes: rows = the common vector length of q_offset/kv_len
    # (scalars broadcast); each lane owns bh/rows query heads in the fold
    rows = max(_veclen(q_offset), _veclen(kv_len))
    for name, x in (("q_offset", q_offset), ("kv_len", kv_len)):
        if _veclen(x) not in (1, rows):
            raise ValueError(f"{name} has {_veclen(x)} rows, expected 1 or "
                             f"{rows}")
    if bh % rows != 0 or k.shape[0] % rows != 0:
        raise ValueError(f"per-row q_offset/kv_len of {rows} rows must "
                         f"divide the folded batch-head counts "
                         f"({bh}, {k.shape[0]})")

    static_vals = (np.asarray([sk], np.int64) if kv_len is None
                   else _concrete(kv_len))
    if static_vals is not None:
        vals = np.clip(static_vals, 0, sk)
        # grid shrinks to the longest lane; shorter lanes pl.when-skip
        static_len = int(vals.max())
        nk_run = max(-(-static_len // kv_block), 1)
        # static full coverage on EVERY lane: no validity mask — the plain
        # self-attention config compiles to the pre-decode kernel body
        full_len = int(vals.min()) >= sk
        kvlen_arr = jnp.broadcast_to(jnp.asarray(vals, jnp.int32), (rows,))
    else:
        nk_run = nk_full  # traced: full grid, pl.when skips dead blocks
        full_len = False
        kvlen_arr = jnp.broadcast_to(
            jnp.asarray(kv_len, jnp.int32).reshape(-1), (rows,))
    qoff_arr = jnp.broadcast_to(
        jnp.asarray(0 if q_offset is None else q_offset,
                    jnp.int32).reshape(-1), (rows,))

    fa = _flash_fn(bool(causal), int(window), q_block, kv_block, nk_run,
                   full_len, None if n_heads is None else int(n_heads),
                   rows, quantized, planner.resolve_interpret(interpret))
    if quantized:
        kbh = k.shape[0]
        return fa(q, k, v, qoff_arr, kvlen_arr,
                  jnp.asarray(k_scale, jnp.float32).reshape(kbh),
                  jnp.asarray(v_scale, jnp.float32).reshape(kbh))
    return fa(q, k, v, qoff_arr, kvlen_arr)
