"""HBP-tiled matmul Pallas kernel.

The paper's Depth-n-MM / Strassen substrate adapted to the MXU: the
recursive quadrant decomposition becomes (bm x bn x bk) VMEM tiles, and the
output tiles are visited in **Morton (BI) order** — the bit-interleaved
layout of §3.2 applied to the grid schedule (shared machinery in
``repro.kernels.morton``), so successive grid steps reuse one of the two
input panels (O(1)-block-sharing across time instead of space).  fp32
accumulation in VMEM scratch; each output tile written once (limited
access).

Tile sizes default to ``None`` = planned from the queried device through
``repro.kernels.planner`` (no hard-coded block constants); pass explicit
values to override.  Ragged shapes snap each override down to the largest
divisor of its axis instead of asserting, and a degenerate snap (prime-ish
dims forcing a sub-sublane tile on a long axis) falls back to the jnp
oracle — or raises, under a ``strict_tiles`` policy.  ``out_dtype`` lets the Strassen-schedule wrapper keep the f32
accumulator through its combination tree instead of rounding at every leaf.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.morton import grid_decode


def _mm_kernel(a_ref, b_ref, out_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(1) == nk - 1)
    def _emit():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "morton",
                                             "interpret", "out_dtype"))
def hbp_matmul(a: jax.Array, b: jax.Array, *, bm: Optional[int] = None,
               bn: Optional[int] = None, bk: Optional[int] = None,
               morton: bool = True, interpret: Optional[bool] = None,
               out_dtype=None) -> jax.Array:
    """C = A @ B with Morton-ordered output tiles.  A: (m, k), B: (k, n)."""
    from repro.kernels import planner

    interpret = planner.resolve_interpret(interpret)
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    out_dtype = jnp.dtype(a.dtype if out_dtype is None else out_dtype)
    if bm is None or bn is None or bk is None:
        plan = planner.plan_matmul(m, k, n, a.dtype)
        bm = bm if bm is not None else plan["bm"]
        bn = bn if bn is not None else plan["bn"]
        bk = bk if bk is not None else plan["bk"]
    # ragged dims snap each tile to the largest divisor of its axis (planner
    # plans are divisor-exact already; this covers explicit/tuned overrides)
    bm = planner.divisor_tile(m, min(int(bm), m))
    bn = planner.divisor_tile(n, min(int(bn), n))
    bk = planner.divisor_tile(k, min(int(bk), k))
    # a degenerate snap (prime-ish dim -> sub-sublane tile on a long axis)
    # would run a catastrophically fine grid; take the jnp oracle instead
    if (bm < 8 <= m) or (bn < 8 <= n) or (bk < 8 <= k):
        from repro.kernels import policy

        if policy.current().strict_tiles:
            raise ValueError(f"hbp_matmul: tiles ({bm}, {bn}, {bk}) "
                             f"degenerate for ({m}, {k}) @ ({k}, {n}); strict "
                             "tiles forbid the jnp.dot fallback")
        return jnp.dot(a.astype(jnp.float32),
                       b.astype(jnp.float32)).astype(out_dtype)
    nm, nn, nk = m // bm, n // bn, k // bk

    decode = grid_decode(nm, nn, morton=morton)
    grid = (nm * nn, nk)

    def a_map(g, kk):
        i, _ = decode(g)
        return (i, kk)

    def b_map(g, kk):
        _, j = decode(g)
        return (kk, j)

    def o_map(g, kk):
        i, j = decode(g)
        return (i, j)

    return pl.pallas_call(
        functools.partial(_mm_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), a_map),
            pl.BlockSpec((bk, bn), b_map),
        ],
        out_specs=pl.BlockSpec((bm, bn), o_map),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(a, b)
