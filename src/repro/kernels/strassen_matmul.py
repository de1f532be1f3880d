"""Strassen-schedule matmul over the HBP-tiled Pallas leaf kernel.

The paper's Type-2 HBP exemplar (Depth-n-MM / Strassen, §3.2: W = n^2.807,
Q = n^lam / (B M^(lam/2 - 1))) realized on the kernel substrate: the
7-product quadrant recursion runs at trace time, reusing the
``_STRASSEN_LHS/RHS/OUT`` combination structure the simulator programs in
``repro.core.algorithms`` (the simulator's MA trees do not track signs —
the numeric kernel adds the matching sign tables below), down to a
planner-chosen ``cutoff`` edge.  Beneath the cutoff each leaf dispatches to
the Morton-ordered ``hbp_matmul`` tile kernel with ``out_dtype=float32``,
so the f32 accumulator survives the whole combination tree: operand
combinations (A11 + A22 etc.) are formed as fused jnp adds feeding the leaf
``pallas_call``s, quadrant combines stay in f32, and only the final result
rounds to the input dtype.

``matmul`` is the registry's dispatch entry point: it runs the requested
``backend`` ("classical", the default, or "strassen", gated per shape by
``planner.plan_matmul_backend``), and registers a custom VJP (dA = g Bᵀ,
dB = Aᵀ g, each a classical product), so model matmuls can route through
the kernels under autodiff.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.algorithms import _STRASSEN_LHS, _STRASSEN_OUT, _STRASSEN_RHS
from repro.kernels.hbp_matmul import hbp_matmul

# Signs for the shared index structure (quadrants 0..3 = 11, 12, 21, 22;
# products 0..6 = Strassen's M1..M7): M6 = (A21 - A11)(B11 + B12) etc.
# ``tests/test_strassen.py`` cross-validates the signed combination against
# the textbook recursion in ``core.algorithms_jax.strassen``.
_LHS_SIGNS = ((1, 1), (1, 1), (1,), (1,), (1, 1), (1, -1), (1, -1))
_RHS_SIGNS = ((1, 1), (1,), (1, -1), (1, -1), (1,), (1, 1), (1, 1))
_OUT_SIGNS = ((1, 1, -1, 1), (1, 1), (1, 1), (1, -1, 1, 1))


def _combo(parts, idxs, signs, out_dtype):
    """Signed sum of quadrants/products: accumulate in f32, emit ``out_dtype``
    (for operand combinations that is the input dtype — one rounding right
    before the leaf's own f32-accumulating dot)."""
    if len(idxs) == 1:
        r = parts[idxs[0]]
        return r if r.dtype == out_dtype else r.astype(out_dtype)
    acc = parts[idxs[0]].astype(jnp.float32)
    for ix, s in zip(idxs[1:], signs[1:]):
        q = parts[ix].astype(jnp.float32)
        acc = acc + q if s > 0 else acc - q
    return acc.astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("cutoff", "bm", "bn", "bk",
                                             "morton", "interpret"))
def strassen_matmul(a: jax.Array, b: jax.Array, *,
                    cutoff: Optional[int] = None, bm: Optional[int] = None,
                    bn: Optional[int] = None, bk: Optional[int] = None,
                    morton: bool = True, interpret: Optional[bool] = None) -> jax.Array:
    """C = A @ B via the Strassen quadrant recursion, classical tiled leaves.

    Ineligible shapes (non-square, or nothing to halve above the cutoff)
    fall straight through to ``hbp_matmul``; tile overrides reach the
    leaves, where ragged leaf edges snap them to divisors.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    from repro.kernels import planner

    if cutoff is None:
        cutoff = planner.strassen_cutoff(a.dtype)
    cutoff = max(int(cutoff), 1)
    if not (m == k == n and n % 2 == 0 and n > cutoff):
        return hbp_matmul(a, b, bm=bm, bn=bn, bk=bk, morton=morton,
                          interpret=interpret)

    dtype = a.dtype
    leaf = functools.partial(hbp_matmul, bm=bm, bn=bn, bk=bk, morton=morton,
                             interpret=interpret, out_dtype=jnp.float32)

    def rec(x, y, edge):
        if edge <= cutoff or edge % 2:
            return leaf(x, y)
        h = edge // 2
        xq = (x[:h, :h], x[:h, h:], x[h:, :h], x[h:, h:])
        yq = (y[:h, :h], y[:h, h:], y[h:, :h], y[h:, h:])
        prods = [rec(_combo(xq, li, ls, dtype), _combo(yq, ri, rs, dtype), h)
                 for li, ls, ri, rs in zip(_STRASSEN_LHS, _LHS_SIGNS,
                                           _STRASSEN_RHS, _RHS_SIGNS)]
        cq = [_combo(prods, oi, os_, jnp.float32)
              for oi, os_ in zip(_STRASSEN_OUT, _OUT_SIGNS)]
        return jnp.concatenate(
            [jnp.concatenate([cq[0], cq[1]], axis=1),
             jnp.concatenate([cq[2], cq[3]], axis=1)], axis=0)

    return rec(a, b, n).astype(dtype)


def _run(a, b, backend, cutoff, bm, bn, bk, morton, interpret):
    """Run the variant: classical unless ``backend`` asks for Strassen; a
    Strassen request without a ``cutoff`` takes the planner's gate and
    cutoff for this shape."""
    if backend == "strassen" and cutoff is None:
        from repro.kernels import planner

        plan = planner.plan_matmul_backend(a.shape[0], a.shape[1],
                                           b.shape[1], a.dtype)
        backend, cutoff = plan["backend"], plan.get("cutoff")
    if backend == "strassen":
        return strassen_matmul(a, b, cutoff=cutoff, bm=bm, bn=bn, bk=bk,
                               morton=morton, interpret=interpret)
    return hbp_matmul(a, b, bm=bm, bn=bn, bk=bk, morton=morton,
                      interpret=interpret)


@functools.lru_cache(maxsize=None)
def _vjp_matmul(backend, cutoff, bm, bn, bk, morton, interpret):
    """custom-VJP wrapper per static config: the forward runs the selected
    variant; the backward's two products (g Bᵀ and Aᵀ g) run classical,
    planner-tiled for their own shapes."""

    @jax.custom_vjp
    def f(a, b):
        return _run(a, b, backend, cutoff, bm, bn, bk, morton, interpret)

    def fwd(a, b):
        return _run(a, b, backend, cutoff, bm, bn, bk, morton, interpret), (a, b)

    def bwd(res, g):
        a, b = res
        da = _run(g, b.T, None, None, None, None, None, True, interpret)
        db = _run(a.T, g, None, None, None, None, None, True, interpret)
        return da.astype(a.dtype), db.astype(b.dtype)

    f.defvjp(fwd, bwd)
    return f


def matmul(a: jax.Array, b: jax.Array, *, backend: Optional[str] = None,
           cutoff: Optional[int] = None, bm: Optional[int] = None,
           bn: Optional[int] = None, bk: Optional[int] = None,
           morton: bool = True, interpret: Optional[bool] = None) -> jax.Array:
    """Backend-dispatching matmul (the registry's ``matmul`` Pallas entry):
    ``backend`` None or "classical" runs ``hbp_matmul``, "strassen" the
    quadrant recursion.  Differentiable (custom VJP)."""
    if backend not in (None, "classical", "strassen"):
        raise ValueError(f"unknown matmul backend {backend!r}; expected "
                         "'classical' or 'strassen'")
    return _vjp_matmul(backend, cutoff, bm, bn, bk, morton, interpret)(a, b)
