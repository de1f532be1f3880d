"""BI-tiled transpose Pallas kernel — the paper's MT algorithm on the MXU.

The recursive BI quadrant swap becomes: visit (bt x bt) tiles in Morton
order (``repro.kernels.morton`` — the BI layout applied to the *grid
schedule*), each grid step reads tile (i, j) and writes its transpose to
tile (j, i).  Every output element written exactly once (limited access);
each task touches exactly two tiles (O(1)-block sharing — the paper's
L(r) = O(1) for MT).

``bt=None`` (the default) plans the tile edge from the queried device via
``repro.kernels.planner``."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.morton import grid_decode


def _transpose_kernel(x_ref, out_ref):
    out_ref[...] = x_ref[...].T


@functools.partial(jax.jit, static_argnames=("bt", "morton", "interpret"))
def bi_transpose(x: jax.Array, *, bt: Optional[int] = None, morton: bool = True,
                 interpret: Optional[bool] = None) -> jax.Array:
    """x: (m, n) -> (n, m), tile-blocked."""
    from repro.kernels import planner

    m, n = x.shape
    if bt is None:
        bt = planner.plan_transpose(m, n, x.dtype)["bt"]
    bt_m, bt_n = min(bt, m), min(bt, n)
    assert m % bt_m == 0 and n % bt_n == 0
    nm, nn = m // bt_m, n // bt_n

    decode = grid_decode(nm, nn, morton=morton)
    grid = (nm * nn,)

    def in_map(g):
        i, j = decode(g)
        return (i, j)

    def out_map(g):
        i, j = decode(g)
        return (j, i)

    return pl.pallas_call(
        _transpose_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bt_m, bt_n), in_map)],
        out_specs=pl.BlockSpec((bt_n, bt_m), out_map),
        out_shape=jax.ShapeDtypeStruct((n, m), x.dtype),
        interpret=planner.resolve_interpret(interpret),
    )(x)
