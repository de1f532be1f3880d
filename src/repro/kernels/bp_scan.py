"""BP prefix-scan Pallas kernel — the paper's PS algorithm as a TPU kernel.

Two BP passes (paper §3.2 'Scans'):
  pass 1 (down): each grid block computes its local inclusive cumsum and its
                 block total (the BP leaf reduction);
  between:       the block totals are exclusive-scanned (the up-tree — tiny,
                 done in jnp on the host program);
  pass 2 (up):   each block adds its prefix offset (the down-distribution).

Block size = the BP leaf size; VMEM tiling via BlockSpec.  Limited access:
every output element written exactly once per pass.  Each grid step holds a
``(row_block, block)`` tile: ``row_block`` is 8 rows when the row count is a
multiple of 8, else every row, so the tile meets the TPU's (8, 128) layout
rule; block totals and offsets travel as ``(nb, rows, 1)`` arrays for the
same reason.  The in-block scan is a Hillis–Steele doubling over the lanes
(``pltpu.roll``): Mosaic has no ``cumsum``.

``block=None`` (the default) plans the leaf size from the queried device via
``repro.kernels.planner``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _lane_cumsum(x):
    """Inclusive prefix sum along the last axis in log2(n) roll-and-add
    steps."""
    axis = x.ndim - 1
    idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    shift = 1
    while shift < x.shape[axis]:
        x = x + jnp.where(idx >= shift, pltpu.roll(x, shift, axis), 0.0)
        shift *= 2
    return x


def _scan_block_kernel(x_ref, out_ref, tot_ref):
    c = _lane_cumsum(x_ref[...].astype(jnp.float32))
    out_ref[...] = c.astype(out_ref.dtype)
    tot_ref[0] = c[:, -1:]


def _add_offset_kernel(y_ref, off_ref, out_ref):
    out_ref[...] = (y_ref[...].astype(jnp.float32)
                    + off_ref[0]).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def bp_scan(x: jax.Array, *, block: Optional[int] = None,
            interpret: Optional[bool] = None) -> jax.Array:
    """Inclusive prefix sum along the last axis.  x: (rows, n)."""
    from repro.kernels import planner

    interpret = planner.resolve_interpret(interpret)
    rows, n = x.shape
    if block is None:
        block = planner.plan_scan(x.shape, x.dtype)["block"]
    block = min(block, n)
    assert n % block == 0, (n, block)
    nb = n // block
    rb = planner.scan_row_block(rows)
    grid = (rows // rb, nb)
    tile = pl.BlockSpec((rb, block), lambda r, i: (r, i))
    col = pl.BlockSpec((1, rb, 1), lambda r, i: (i, r, 0))

    local, totals = pl.pallas_call(
        _scan_block_kernel,
        grid=grid,
        in_specs=[tile],
        out_specs=[tile, col],
        out_shape=[
            jax.ShapeDtypeStruct((rows, n), x.dtype),
            jax.ShapeDtypeStruct((nb, rows, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x)

    # exclusive scan of the block totals (the up-tree: nb values per row)
    offsets = jnp.cumsum(totals, axis=0) - totals

    return pl.pallas_call(
        _add_offset_kernel,
        grid=grid,
        in_specs=[tile, col],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        interpret=interpret,
    )(local, offsets)
