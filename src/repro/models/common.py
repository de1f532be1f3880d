"""Shared model blocks: norms, RoPE, attention (GQA / sliding-window / cross),
gated MLP, embeddings, and the blockwise (BP-structured) attention used for
long sequences.

The blockwise attention is the paper's BP computation made concrete: the
online-softmax combine ``(m,l,acc) ⊕ (m',l',acc')`` is associative, so the
KV-block loop is exactly a BP reduce (down-pass = per-block partial attention,
up-pass = combine).  On TPU the per-block body becomes the Pallas kernel in
``repro.kernels.flash_attention``; here we express the same computation with
``jax.lax.scan`` so XLA sees a small, memory-bounded loop.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.sharding_hints import constrain  # noqa: F401  (re-exported)


# ---------------------------------------------------------------------------
# initialization helpers
# ---------------------------------------------------------------------------

def dense_init(key, shape, dtype, scale: Optional[float] = None):
    """Truncated-normal fan-in init."""
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std).astype(dtype)


def embed_lookup(embed, tokens):
    """Token embedding lookup with the table replicated over the tensor axis.

    The table is (vocab@tp, d@fsdp) for the logits matmul; for the *lookup*
    an all-gather of the small table over tp (~MBs) beats the all-reduce of
    the (b, s, d) activation (~GBs) that GSPMD otherwise emits for a
    vocab-sharded gather.  PWS-planner rule: steal the cheap fork.
    """
    table = constrain(embed, None, "*")  # replicate vocab over tp; keep fsdp dim
    return table[tokens]


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rms_norm(x, weight, eps: float = 1e-6):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + weight.astype(jnp.float32))).astype(dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., seq, heads, head_dim); positions: (..., seq) int32."""
    hd = x.shape[-1]
    inv = rope_freqs(hd, theta)  # (hd/2,)
    ang = positions[..., :, None].astype(jnp.float32) * inv  # (..., s, hd/2)
    cos = jnp.cos(ang)[..., :, None, :]
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def _mask_bias(q_pos, k_pos, *, causal: bool, window: Optional[int]):
    """(q, k) additive bias from positions; built from iota (no big
    constants).  ``q_pos`` may be batched (b, sq) — the continuous-batching
    decode where each row sits at its own position — giving a (b, q, k)
    bias."""
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    ok = jnp.ones(jnp.broadcast_shapes(qp.shape, kp.shape), dtype=bool)
    if causal:
        ok &= kp <= qp
    if window is not None:
        ok &= kp > (qp - window)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def repeat_kv(k, n_rep: int):
    """(b, t, kvh, hd) -> (b, t, kvh*n_rep, hd)"""
    if n_rep == 1:
        return k
    b, t, kvh, hd = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, t, kvh, n_rep, hd)).reshape(b, t, kvh * n_rep, hd)


def attention_dense(q, k, v, q_pos, k_pos, *, causal=True, window=None, softmax_scale=None):
    """Reference attention, materializes (q, k) scores.  Used for short
    sequences and decode (q_len == 1).

    GQA keeps k/v at their native head count: q folds its per-group heads
    into the einsum instead of repeating the (potentially cache-sized) k/v
    tensors — on every decode step the cache streams through once, ungrown.
    bf16 operands + f32 accumulation (native MXU semantics): no f32 copy of
    the cache is ever materialized either."""
    b, sq, h, hd = q.shape
    kvh = k.shape[2]
    n_rep = h // kvh
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(hd)
    qg = q.reshape(b, sq, kvh, n_rep, hd)  # head h = kv_head * n_rep + rep
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k,
                        preferred_element_type=jnp.float32) * scale
    scores = constrain(scores, "batch", "kv_heads", "*", "*", "*")
    bias = _mask_bias(q_pos, k_pos, causal=causal, window=window)
    # batched (b, q, k) bias (per-row decode positions) aligns on batch
    bias = bias[:, None, None] if bias.ndim == 3 else bias[None, None, None]
    scores = scores + bias
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    out = out.reshape(b, sq, h, hd)
    return constrain(out.astype(q.dtype), "batch", "*", "heads", "*")


def _blockwise_fwd_inner(qs, ks, vs, qp, kp, window, *, causal, scale, n_rep):
    """Forward pass over (nq, b, h, qb, hd) q-blocks and (nk, b, kvh, kb, hd)
    kv-blocks.  Returns (out_blocks, lse_blocks) — the BP down-pass with the
    online-softmax combine as the up-pass."""
    nq, b, h, q_block, hd = qs.shape
    kvh = h // n_rep

    def per_qblock(carry, qi):
        qb, qpb = qi

        def per_kvblock(state, ki):
            m, l, acc = state
            kb, vb, kpb = ki
            kb_len = kb.shape[2]
            if n_rep > 1:
                # native KV heads: fold q's per-group heads into the einsum
                # (head h = kv_head * n_rep + rep) — the oracle shares the
                # kernel's no-copy discipline, no block ever repeats
                qg = qb.reshape(b, kvh, n_rep, q_block, hd)
                s = jnp.einsum("bgrqd,bgkd->bgrqk", qg, kb,
                               preferred_element_type=jnp.float32,
                               ).reshape(b, h, q_block, kb_len) * scale
            else:
                s = jnp.einsum("bhqd,bhkd->bhqk", qb, kb,
                               preferred_element_type=jnp.float32) * scale
            s = constrain(s, "batch", "heads", "*", "*")
            s = s + _mask_bias(qpb, kpb, causal=causal, window=window)[None, None]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            correction = jnp.exp(m - m_new)
            l_new = l * correction + jnp.sum(p, axis=-1)
            if n_rep > 1:
                pg = p.reshape(b, kvh, n_rep, q_block, kb_len)
                pv = jnp.einsum("bgrqk,bgkd->bgrqd", pg.astype(vb.dtype), vb,
                                preferred_element_type=jnp.float32,
                                ).reshape(b, h, q_block, hd)
            else:
                pv = jnp.einsum("bhqk,bhkd->bhqd", p.astype(vb.dtype), vb,
                                preferred_element_type=jnp.float32)
            acc_new = acc * correction[..., None] + pv
            return (m_new, l_new, acc_new), None

        init = (
            jnp.full((b, h, q_block), NEG_INF, jnp.float32),
            jnp.zeros((b, h, q_block), jnp.float32),
            jnp.zeros((b, h, q_block, hd), jnp.float32),
        )
        (m, l, acc), _ = jax.lax.scan(per_kvblock, init, (ks, vs, kp))
        l_safe = jnp.maximum(l, 1e-30)
        out = acc / l_safe[..., None]
        lse = m + jnp.log(l_safe)
        return carry, (out, lse)

    _, (outs, lses) = jax.lax.scan(per_qblock, None, (qs, qp))
    return outs, lses  # (nq, b, h, qb, hd), (nq, b, h, qb)


def _make_blockwise(causal: bool, scale: float, q_block: int, kv_block: int,
                    n_rep: int):
    """Build a custom-VJP blockwise attention for fixed static config.
    The (possibly traced) sliding window is a real argument — never closed
    over — so per-layer windows can flow through ``lax.scan``.

    The backward recomputes P per block (flash-attention backward), so no
    O(sq*sk) tensor is ever saved — the paper's limited-access discipline
    applied to autodiff residuals.
    """

    @jax.custom_vjp
    def fa(qs, ks, vs, qp, kp, warr):
        outs, _ = _blockwise_fwd_inner(qs, ks, vs, qp, kp, warr[0], causal=causal,
                                       scale=scale, n_rep=n_rep)
        return outs

    def fa_fwd(qs, ks, vs, qp, kp, warr):
        outs, lses = _blockwise_fwd_inner(qs, ks, vs, qp, kp, warr[0], causal=causal,
                                          scale=scale, n_rep=n_rep)
        return outs, (qs, ks, vs, qp, kp, warr, outs, lses)

    def fa_bwd(res, g):
        qs, ks, vs, qp, kp, warr, outs, lses = res
        window = warr[0]
        nq, b, h, q_block, hd = qs.shape
        nk = ks.shape[0]
        kvh = ks.shape[2]
        # D = rowsum(dO * O)
        delta = jnp.sum(g.astype(jnp.float32) * outs.astype(jnp.float32), axis=-1)

        def per_qblock(carry, xs):
            dk_acc, dv_acc = carry  # (nk, b, kvh, kb, hd) fp32
            qb, qpb, ob, lseb, gb, db = xs

            def per_kvblock(dq, ki):
                (kb, vb, kpb, dk_a, dv_a) = ki
                kb_len = kb.shape[2]
                gf = gb
                if n_rep > 1:
                    # grouped einsums at the native KV head count: the r axis
                    # contracts away in the dk/dv products, so the group sum
                    # happens inside the einsum — no repeated block, no
                    # post-hoc reshape-sum
                    qg = qb.reshape(b, kvh, n_rep, q_block, hd)
                    gg = gf.reshape(b, kvh, n_rep, q_block, hd)
                    s = jnp.einsum("bgrqd,bgkd->bgrqk", qg, kb,
                                   preferred_element_type=jnp.float32,
                                   ).reshape(b, h, q_block, kb_len) * scale
                    s = s + _mask_bias(qpb, kpb, causal=causal,
                                       window=window)[None, None]
                    p = jnp.exp(s - lseb[..., None])  # (b,h,qb,kb) f32
                    pg = p.reshape(b, kvh, n_rep, q_block, kb_len)
                    dv_blk = jnp.einsum("bgrqk,bgrqd->bgkd", pg.astype(gf.dtype),
                                        gg, preferred_element_type=jnp.float32)
                    dp = jnp.einsum("bgrqd,bgkd->bgrqk", gg, vb,
                                    preferred_element_type=jnp.float32,
                                    ).reshape(b, h, q_block, kb_len)
                    ds = p * (dp - db[..., None]) * scale
                    dsg = ds.reshape(b, kvh, n_rep, q_block, kb_len)
                    dq = dq + jnp.einsum("bgrqk,bgkd->bgrqd",
                                         dsg.astype(kb.dtype), kb,
                                         preferred_element_type=jnp.float32,
                                         ).reshape(b, h, q_block, hd)
                    dk_blk = jnp.einsum("bgrqk,bgrqd->bgkd",
                                        dsg.astype(qb.dtype), qg,
                                        preferred_element_type=jnp.float32)
                else:
                    s = jnp.einsum("bhqd,bhkd->bhqk", qb, kb,
                                   preferred_element_type=jnp.float32) * scale
                    s = s + _mask_bias(qpb, kpb, causal=causal,
                                       window=window)[None, None]
                    p = jnp.exp(s - lseb[..., None])  # (b,h,qb,kb) f32
                    dv_blk = jnp.einsum("bhqk,bhqd->bhkd", p.astype(gf.dtype),
                                        gf, preferred_element_type=jnp.float32)
                    dp = jnp.einsum("bhqd,bhkd->bhqk", gf, vb,
                                    preferred_element_type=jnp.float32)
                    ds = p * (dp - db[..., None]) * scale
                    dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds.astype(kb.dtype),
                                         kb, preferred_element_type=jnp.float32)
                    dk_blk = jnp.einsum("bhqk,bhqd->bhkd", ds.astype(qb.dtype),
                                        qb, preferred_element_type=jnp.float32)
                return dq, (dk_a + dk_blk, dv_a + dv_blk)

            dq0 = jnp.zeros((b, h, q_block, hd), jnp.float32)
            dq, (dk_new, dv_new) = jax.lax.scan(
                per_kvblock, dq0, (ks, vs, kp, dk_acc, dv_acc))
            return (dk_new, dv_new), dq

        dk0 = jnp.zeros((nk,) + ks.shape[1:], jnp.float32)
        dv0 = jnp.zeros((nk,) + vs.shape[1:], jnp.float32)
        (dk, dv), dqs = jax.lax.scan(per_qblock, (dk0, dv0),
                                     (qs, qp, outs, lses, g, delta))
        return (dqs.astype(qs.dtype), dk.astype(ks.dtype), dv.astype(vs.dtype),
                None, None, None)

    fa.defvjp(fa_fwd, fa_bwd)
    return fa


def attention_blockwise(
    q,
    k,
    v,
    q_pos,
    k_pos,
    *,
    causal=True,
    window=None,
    softmax_scale=None,
    q_block: int = 512,
    kv_block: int = 1024,
):
    """Flash-style blockwise attention as a BP computation over KV blocks
    with a flash backward (custom VJP — O(block^2) working set, never
    O(sq*sk)).

    The online-softmax combine ``(m,l,acc)`` is associative: the KV-block
    loop is a BP reduce (paper Def. 3.2), and the Pallas kernel twin is
    ``repro.kernels.flash_attention``.
    """
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    kvh = k.shape[2]
    n_rep = h // kvh
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(hd)

    q_block = min(q_block, sq)
    kv_block = min(kv_block, sk)
    assert sq % q_block == 0 and sk % kv_block == 0, (sq, q_block, sk, kv_block)
    nq, nk = sq // q_block, sk // kv_block

    qs = q.reshape(b, nq, q_block, h, hd).transpose(1, 0, 3, 2, 4)
    ks = k.reshape(b, nk, kv_block, kvh, hd).transpose(1, 0, 3, 2, 4)
    vs = v.reshape(b, nk, kv_block, kvh, hd).transpose(1, 0, 3, 2, 4)
    qp = q_pos.reshape(nq, q_block)
    kp = k_pos.reshape(nk, kv_block)

    warr = jnp.asarray([(1 << 30) if window is None else window], jnp.int32)
    fa = _make_blockwise(causal, scale, q_block, kv_block, n_rep)
    outs = fa(qs, ks, vs, qp, kp, warr)  # (nq, b, h, qb, hd)
    out = outs.transpose(1, 0, 3, 2, 4).reshape(b, sq, h, hd).astype(q.dtype)
    return constrain(out, "batch", "*", "heads", "*")


def attention_banded_local(q, k, v, q_pos, k_pos, *, window: int, softmax_scale=None):
    """Beyond-paper optimized sliding-window attention: attend each query
    block only to its own and the previous KV block (exact when
    ``window <= block``).  This is the paper's O(1)-block-sharing principle:
    each task (query block) touches O(1) KV blocks.

    Compute drops from O(s^2) to O(s * 2*block).
    """
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    assert sq == sk, "banded local attention expects self-attention"
    block = max(window, 128)
    if sq % block != 0 or sq <= 2 * block:
        return attention_blockwise(q, k, v, q_pos, k_pos, causal=True, window=window,
                                   softmax_scale=softmax_scale)
    kvh = k.shape[2]
    n_rep = h // kvh
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(hd)
    nb = sq // block

    k = repeat_kv(k, n_rep)
    v = repeat_kv(v, n_rep)
    qs = q.reshape(b, nb, block, h, hd)
    ks = k.reshape(b, nb, block, h, hd)
    vs = v.reshape(b, nb, block, h, hd)
    # previous block (block 0's "previous" is zeros and fully masked)
    ks_prev = jnp.concatenate([jnp.zeros_like(ks[:, :1]), ks[:, :-1]], axis=1)
    vs_prev = jnp.concatenate([jnp.zeros_like(vs[:, :1]), vs[:, :-1]], axis=1)
    kcat = jnp.concatenate([ks_prev, ks], axis=2)  # (b, nb, 2*block, h, hd)
    vcat = jnp.concatenate([vs_prev, vs], axis=2)

    qp = q_pos.reshape(nb, block)
    kp_local = q_pos.reshape(nb, block)
    kp_prev = jnp.concatenate([jnp.full((1, block), -10**9, jnp.int32), kp_local[:-1]], axis=0)
    kp_cat = jnp.concatenate([kp_prev, kp_local], axis=1)  # (nb, 2*block)

    s = jnp.einsum("bnqhd,bnkhd->bnhqk", qs.astype(jnp.float32), kcat.astype(jnp.float32)) * scale
    ok = (kp_cat[:, None, :] <= qp[:, :, None]) & (kp_cat[:, None, :] > qp[:, :, None] - window)
    s = s + jnp.where(ok, 0.0, NEG_INF)[None, :, None]  # (b, nb, h, q, k)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bnhqk,bnkhd->bnqhd", p.astype(vcat.dtype), vcat)
    return out.reshape(b, sq, h, hd).astype(q.dtype)


def attention_blockwise_triangular(q, k, v, q_pos, k_pos, *, window=None,
                                   softmax_scale=None, q_block: int = 512):
    """Beyond-paper optimization: causal blockwise attention that SKIPS
    fully-masked (future) KV blocks by unrolling the q-block loop — q block i
    attends KV blocks 0..i only.  Halves attention compute and the
    scores-tensor traffic vs the masked full grid.  Exact (the skipped blocks
    contribute nothing)."""
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    assert sq == sk, "triangular path is for self-attention"
    kvh = k.shape[2]
    n_rep = h // kvh
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(hd)
    q_block = min(q_block, sq)
    assert sq % q_block == 0
    nq = sq // q_block

    qs = q.reshape(b, nq, q_block, h, hd).transpose(1, 0, 3, 2, 4)
    ks = k.reshape(b, nq, q_block, kvh, hd).transpose(1, 0, 3, 2, 4)
    vs = v.reshape(b, nq, q_block, kvh, hd).transpose(1, 0, 3, 2, 4)
    qp = q_pos.reshape(nq, q_block)
    kp = k_pos.reshape(nq, q_block)
    warr = jnp.asarray([(1 << 30) if window is None else window], jnp.int32)

    fa = _make_blockwise(True, scale, q_block, q_block, n_rep)
    outs = []
    for i in range(nq):
        o = fa(qs[i : i + 1], ks[: i + 1], vs[: i + 1], qp[i : i + 1], kp[: i + 1],
               warr)
        outs.append(o)
    out = jnp.concatenate(outs, 0).transpose(1, 0, 3, 2, 4).reshape(b, sq, h, hd)
    return constrain(out.astype(q.dtype), "batch", "*", "heads", "*")


def _attention_via_kernel(q, k, v, q_pos, k_pos, *, causal, window, q_block,
                          kv_block, k_scale=None, v_scale=None, kv_len=None):
    """Adapter onto the registry's flash-attention Pallas kernel: fold heads
    into batch (batch-major, head = kv_head * n_rep + rep), dispatch, unfold.
    K/V stay at their NATIVE head count — the kernel's kv ``index_map``
    routes each query head's grid steps into its group's KV row, so the
    cache-sized ``repeat_kv`` copy the old adapter paid per call never
    exists; the kernel's rep-aware transposed grid group-sums dk/dv.

    CONTRACT: with ``kv_len=None``, positions must be contiguous ranges
    (q row i at ``q_pos[0] + i``, key j at ``k_pos[0] + j``) whenever they
    matter (causal or windowed masking) — linear DecodeCache layouts and
    fresh self-attention satisfy this, and the offset/length vectors are
    derived from the positions.  An explicit ``kv_len`` (scalar or per-row
    (b,)) overrides the derivation for layouts whose keys are raw cache
    slots starting at 0 — ``RingKV``'s wrap-aware mapping passes
    ``q_offset = pos`` and ``kv_len = min(pos + 1, C)`` so a wrapped row
    attends its whole ring (slot order is a softmax permutation) and an
    unwrapped row its contiguous prefix; ``kv_len == 0`` rows emit exact
    zeros (the kernel's ``l_safe`` guard).  Under causal masking KV blocks
    past ``kv_len`` are skipped instead of computed-then-masked.
    ``k_scale``/``v_scale`` — per (batch, kv_head) f32, paired with an int8
    k/v — ride to the kernel's in-block dequant."""
    from repro.core.sharding_hints import kernel_map, mesh_axis
    from repro.kernels import registry

    b, sq, h, hd = q.shape
    sk = k.shape[1]
    kvh = k.shape[2]

    if kv_len is not None:
        # explicit valid-key counts: keys are raw cache slots (base 0), so
        # the query offset is the position itself (per-row or scalar)
        q_offset = (q_pos[:, 0] if q_pos.ndim == 2 else q_pos[:1]).astype(jnp.int32)
        kv_len = jnp.asarray(kv_len, jnp.int32)
        if kv_len.ndim == 0:
            kv_len = kv_len[None]
    elif sq == sk:
        q_offset = kv_len = None  # zero-offset self-attention: static path
    elif q_pos.ndim == 2:
        # per-row decode: each batch lane carries its own position, so the
        # kernel gets (b,) offset/length vectors (SMEM; batch-major fold
        # means lane = bh // h, matching the kernel's rows contract)
        q_offset = (q_pos[:, 0] - k_pos[0]).astype(jnp.int32)
        kv_len = (jnp.minimum(q_offset + sq, sk).astype(jnp.int32)
                  if causal else None)
    else:
        q_offset = (q_pos[0] - k_pos[0]).astype(jnp.int32)
        kv_len = jnp.minimum(q_offset + sq, sk) if causal else None

    # forward overrides only when divisor-exact; else the per-shape plan wins
    qb = q_block if (q_block and sq % min(q_block, sq) == 0) else None
    kb = kv_block if (kv_block and sk % min(kv_block, sk) == 0) else None
    quantized = k_scale is not None

    def kernel(q, k, v, *rest):
        # runs on one shard's block: batch rows and head groups are
        # independent, so any split along them is exact
        lb, lh, lkvh = q.shape[0], q.shape[2], k.shape[2]
        rest = list(rest)
        qo = rest.pop(0) if q_offset is not None else None
        kl = rest.pop(0) if kv_len is not None else None
        kwargs = {}
        if lkvh != lh:
            kwargs["n_heads"] = lh
        if quantized:
            kwargs["k_scale"] = rest[0].reshape(lb * lkvh)
            kwargs["v_scale"] = rest[1].reshape(lb * lkvh)

        def fold(x):
            return x.transpose(0, 2, 1, 3).reshape(lb * x.shape[2],
                                                   x.shape[1], hd)

        out = registry.dispatch(
            "attention", fold(q), fold(k), fold(v), causal=causal,
            window=0 if window is None else int(window),
            q_offset=qo, kv_len=kl, impl="pallas",
            q_block=qb, kv_block=kb, **kwargs,
        )
        return out.reshape(lb, lh, sq, hd).transpose(0, 2, 1, 3)

    # heads split with their kv group only when both divide the same way
    # (a query head's group must live on its shard); else both replicate
    heads = "heads" if (mesh_axis("heads", h) is not None
                        and mesh_axis("kv_heads", kvh) is not None) else None
    kv_heads = "kv_heads" if heads else None
    args = [q, k, v]
    in_axes = [("batch", None, heads, None),
               ("batch", None, kv_heads, None),
               ("batch", None, kv_heads, None)]
    for vec in (q_offset, kv_len):
        if vec is not None:
            vec = jnp.asarray(vec, jnp.int32)
            args.append(vec)
            # a per-row (b,) vector splits with the batch; a shared one
            # (scalar or length 1) goes to every shard
            in_axes.append(("batch",) if vec.shape == (b,) and b > 1
                           else (None,) * vec.ndim)
    if quantized:
        for sc in (k_scale, v_scale):
            args.append(jnp.asarray(sc, jnp.float32).reshape(b, kvh))
            in_axes.append(("batch", kv_heads))
    return kernel_map(kernel, args, in_axes, ("batch", None, heads, None),
                      q.shape)


def attention(q, k, v, q_pos, k_pos, *, causal=True, window=None, softmax_scale=None,
              use_banded_local: bool = False, block_threshold: int = 2048,
              q_block: int = 512, kv_block: int = 1024,
              causal_block_skip: bool = False, k_scale=None, v_scale=None,
              kv_len=None):
    """Dispatch: dense for small/decode, blockwise for long, banded for local,
    triangular for causal long self-attention when block-skip is enabled.

    The backend is the ambient execution policy's call, resolved through
    ``registry.resolve`` — no per-call knob.  "jnp" keeps the XLA paths,
    whose blockwise variant carries the flash custom VJP; "pallas" routes
    the registry's flash kernel, which covers cached decode (query offset +
    KV valid-length) and registers its own recomputation backward, so both
    training and the serving prefill/decode loop share one resolution.
    ``resolve`` consults the kernel's capability metadata (``has_vjp``; the
    ``needs`` gate rejects custom softmax scales and traced scan-carried
    windows — the kernel's window/causal are static kwargs).  The kernel
    route assumes contiguous position ranges UNLESS the caller passes an
    explicit ``kv_len`` — the ``RingKV`` layout does, mapping its wrapped
    rows onto the kernel's per-row vectors (see
    :func:`_attention_via_kernel`), which is what lets the windowed decode
    cache ride the same kernel as every linear layout; cross-attention with
    meaningless positions is fine too since it is non-causal/unwindowed.
    The jnp routes ignore ``kv_len`` — their masks come from the true
    positions (``RingKV.slot_positions``).  Banded-local is a model-level
    algorithm choice, so it stays on its jnp path regardless of the
    resolved backend.

    ``k_scale``/``v_scale`` — per-(batch, kv_head) f32, paired with an int8
    ``k``/``v`` — reach the kernel's in-block dequant on the pallas route;
    every jnp route dequantizes up front (cache-sized f32 copy: the oracle
    pays what the kernel avoids, which is the point of the kernel)."""
    from repro.kernels import registry

    sq, sk = q.shape[1], k.shape[1]
    impl = registry.resolve("attention", softmax_scale=softmax_scale,
                            window=window)
    if impl == "pallas" and not use_banded_local:
        return _attention_via_kernel(q, k, v, q_pos, k_pos, causal=causal,
                                     window=window, q_block=q_block,
                                     kv_block=kv_block, k_scale=k_scale,
                                     v_scale=v_scale, kv_len=kv_len)
    if k_scale is not None:
        k = (k.astype(jnp.float32) * k_scale[:, None, :, None]).astype(q.dtype)
        v = (v.astype(jnp.float32) * v_scale[:, None, :, None]).astype(q.dtype)
    if window is not None and use_banded_local and sq == sk and sq > 2 * max(window, 128):
        return attention_banded_local(q, k, v, q_pos, k_pos, window=window,
                                      softmax_scale=softmax_scale)
    if sq == 1 or (sq * sk <= block_threshold * block_threshold):
        return attention_dense(q, k, v, q_pos, k_pos, causal=causal, window=window,
                               softmax_scale=softmax_scale)
    if causal and causal_block_skip and sq == sk:
        return attention_blockwise_triangular(q, k, v, q_pos, k_pos, window=window,
                                              softmax_scale=softmax_scale,
                                              q_block=max(q_block, kv_block))
    return attention_blockwise(q, k, v, q_pos, k_pos, causal=causal, window=window,
                               softmax_scale=softmax_scale, q_block=q_block,
                               kv_block=kv_block)


# ---------------------------------------------------------------------------
# quantized KV cache (the attention kv_dtype variant)
# ---------------------------------------------------------------------------

def kv_cache_dtype(default):
    """The serving KV-cache dtype under the ambient policy: an attention
    ``kv_dtype`` variant (``--impl 'attention=pallas:kv_dtype=int8'``)
    selects the int8 cache; anything else keeps ``default``.  Returns
    ``(dtype, quantized)``."""
    from repro.kernels import policy

    name = policy.current().variant_for("attention").get("kv_dtype")
    if name in ("int8", "i8"):
        return jnp.int8, True
    return default, False


def kv_scale(x, valid=None):
    """Per-(batch, kv_head) symmetric int8 scale for a (b, s, kvh, hd) k or v
    slab: absmax / 127, floored so an all-zero head still divides cleanly.
    ``valid`` (optional, traced ok; scalar or per-row (b,)) restricts the
    absmax to the first ``valid`` sequence positions — a zero-padded prefill
    chunk must not let pad-token k/v widen the scales that the rest of the
    request will quantize with."""
    ax = jnp.abs(x.astype(jnp.float32))
    if valid is not None:
        v = jnp.asarray(valid)
        v = v[:, None, None, None] if v.ndim == 1 else v
        ok = jnp.arange(x.shape[1])[None, :, None, None] < v
        ax = jnp.where(ok, ax, 0.0)
    amax = jnp.max(ax, axis=(1, 3))  # (b, kvh)
    return jnp.maximum(amax / 127.0, 1e-8)


def quantize_kv(x, scale):
    """Quantize a (b, s, kvh, hd) slab to int8 with the per-(b, kvh)
    ``scale`` (see :func:`kv_scale`); round-to-nearest, clipped."""
    q = jnp.round(x.astype(jnp.float32) / scale[:, None, :, None])
    return jnp.clip(q, -127, 127).astype(jnp.int8)


# ---------------------------------------------------------------------------
# MLP / projections through the kernel registry
# ---------------------------------------------------------------------------

def project(x, w):
    """x: (..., d) @ w: (d, f) -> (..., f), backend resolved by the ambient
    execution policy.  The pallas route folds the leading dims and
    dispatches the registry's matmul (:func:`_matmul_kernel`: planner-tiled,
    classical schedule, autotune-overlaid, differentiable via the kernel's
    custom VJP); jnp keeps the XLA einsum."""
    from repro.kernels import registry

    if registry.resolve("matmul") == "pallas":
        lead = x.shape[:-1]
        out = _matmul_kernel(x.reshape(-1, x.shape[-1]), w)
        return out.reshape(*lead, w.shape[-1])
    return jnp.einsum("...d,df->...f", x, w)


def _matmul_kernel(a, b, out_axis: str = "ffn"):
    """The registry's matmul kernel on (m, k) @ (k, n), once per shard of a
    bound mesh (``kernel_map``): token rows split over the batch axes and
    output columns over the ``out_axis`` binding — only where each shard's
    columns stay lane-aligned (the kernel tiles them by 128) — the
    contraction whole.

    The schedule is the planner's: classical, so each token row's result
    depends on that row alone, whatever requests share the launch, unless
    the policy asks for Strassen (``planner.plan_matmul``)."""
    from repro.core.sharding_hints import kernel_map, mesh_axis
    from repro.kernels import registry

    if mesh_axis(out_axis, b.shape[1], multiple=128) is None:
        out_axis = None
    return kernel_map(
        lambda x, y: registry.dispatch("matmul", x, y, impl="pallas"),
        (a, b), (("batch", None), (None, out_axis)), ("batch", out_axis),
        (a.shape[0], b.shape[1]))


def qkv_project(x, wq, wk, wv):
    """The attention-block input projections, policy-fusable: under a
    ``qkv_fused`` variant on the matmul op (``--impl
    'matmul=pallas:qkv_fused=true'`` or ``RunOptions.fused_qkv``) the three
    per-block projections collapse into ONE ``(d, hq+hk+hv)`` matmul over
    concatenated weights — one planned kernel launch streaming ``x`` once
    instead of three launches streaming it three times — then split back.
    Without the variant: three :func:`project` calls (each still
    policy-routed).  Numerically identical either way (same contractions,
    independent output columns)."""
    from repro.kernels import policy

    if policy.current().variant_for("matmul").get("qkv_fused"):
        w = jnp.concatenate([wq, wk, wv], axis=1)
        fused = project(x, w)
        q, k, v = jnp.split(fused, [wq.shape[1], wq.shape[1] + wk.shape[1]],
                            axis=-1)
        return q, k, v
    return project(x, wq), project(x, wk), project(x, wv)


def attn_out_project(o, wo):
    """Attention epilogue: (b, s, h, hd) heads -> (b, s, d) through the
    output projection, without materializing the flattened (b*s, h*hd)
    reshape as a separate tensor on the jnp route.  The pallas route folds
    the head axes into the registry matmul's contraction dim (one planned
    kernel, the fold is free — same buffer); the jnp route contracts the
    head axes directly in the einsum."""
    b, s, h, hd = o.shape
    from repro.kernels import registry

    if registry.resolve("matmul") == "pallas":
        out = _matmul_kernel(o.reshape(b * s, h * hd), wo.reshape(h * hd, -1))
        return out.reshape(b, s, -1)
    return jnp.einsum("bshd,hdf->bsf", o, wo.reshape(h, hd, -1))


def expert_project(h, w):
    """Per-expert matmul h: (..., E, C, d) @ w: (E, d, f) -> (..., E, C, f)
    (the MoE expert FFN products).  The pallas route vmaps :func:`project`
    over the expert axis — pallas_call batching turns the expert dim into
    one more grid dimension, so each expert's slab stays a registry-planned
    kernel call; jnp keeps the batched einsum."""
    from repro.kernels import registry

    if registry.resolve("matmul") == "pallas":
        return jax.vmap(project, in_axes=(-3, 0), out_axes=-3)(h, w)
    return jnp.einsum("...ecd,edf->...ecf", h, w)


def gated_mlp(x, w_gate, w_up, w_down):
    """SwiGLU MLP; the three projections resolve their backend through the
    ambient policy (see :func:`project`)."""
    g = project(x, w_gate)
    u = project(x, w_up)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    h = constrain(h, *(["batch"] + ["*"] * (h.ndim - 2) + ["ffn"]))
    out = project(h, w_down)
    if out.ndim == 3:
        return constrain(out, "batch", "seq", "*")
    return constrain(out, *(["batch"] + ["*"] * (out.ndim - 1)))


def logits_matmul(h, embed_out):
    """Output-logits product h @ embed_outᵀ in fp32.  h: (..., d),
    embed_out: (V, d) -> (..., V).  The hottest serve-path matmul: the
    pallas route dispatches the registry's kernel (:func:`_matmul_kernel`)."""
    from repro.kernels import registry

    if registry.resolve("matmul") == "pallas":
        lead = h.shape[:-1]
        out = _matmul_kernel(h.reshape(-1, h.shape[-1]), embed_out.T,
                             out_axis="vocab")
        return out.reshape(*lead, embed_out.shape[0]).astype(jnp.float32)
    return jnp.einsum("...d,vd->...v", h, embed_out).astype(jnp.float32)


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

def chunked_softmax_xent(hidden, embed_out, labels, *, chunk: int = 512):
    """Cross-entropy computed in sequence chunks so the (tokens, vocab) logits
    tensor never materializes in full (the paper's principle of bounding the
    working set of a task; each chunk is one BP leaf).  The per-chunk logits
    matmul resolves its backend through the ambient policy (the matmul
    kernel's custom VJP keeps the pallas route differentiable under the
    chunk remat).

    hidden: (b, s, d);  embed_out: (V, d);  labels: (b, s) int32 with -100 pad.
    Returns mean loss (fp32 scalar).
    """
    b, s, d = hidden.shape
    chunk = min(chunk, s)
    if s % chunk != 0:
        chunk = s  # fallback: single chunk
    n = s // chunk
    hs = hidden.reshape(b, n, chunk, d).transpose(1, 0, 2, 3)
    ls = labels.reshape(b, n, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def per_chunk(carry, xs):
        h, lab = xs
        h = constrain(h, "batch", "*", "*")
        logits = logits_matmul(h, embed_out)
        logits = constrain(logits, "batch", "*", "vocab")
        lse = jax.nn.logsumexp(logits, axis=-1)
        valid = lab >= 0
        safe = jnp.where(valid, lab, 0)
        picked = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
        nll = jnp.where(valid, lse - picked, 0.0)
        return (carry[0] + jnp.sum(nll), carry[1] + jnp.sum(valid)), None

    (tot, cnt), _ = jax.lax.scan(per_chunk, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)), (hs, ls))
    return tot / jnp.maximum(cnt, 1.0)
