"""Logical-axis sharding hints.

Models remain resource-oblivious (the paper's contract: algorithms never
mention p, M, B).  They annotate tensors with *logical* axis names
("batch", "heads", "ffn", "experts", "vocab"); the launcher binds logical
names to mesh axes before tracing.  Outside a binding context the hints are
no-ops, so unit tests and single-device runs are untouched.

This is the activation-side half of the PWS planner: the weight-side half
lives in ``repro.core.planner``.  :func:`kernel_map` applies the same
bindings to Pallas kernel launches, which XLA cannot partition: it runs
them per shard.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Optional, Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

# logical name -> mesh axis (str or tuple) binding
_RULES: contextvars.ContextVar[Optional[dict]] = contextvars.ContextVar(
    "sharding_rules", default=None
)

UNCONSTRAINED = P.UNCONSTRAINED


@contextlib.contextmanager
def axis_rules(rules: dict[str, Union[str, tuple, None]], mesh=None):
    """Bind logical axis names to mesh axes.  Example:
    ``axis_rules({"batch": ("pod", "data"), "heads": "model", ...}, mesh)``."""
    sizes = dict(mesh.shape) if mesh is not None else {}
    token = _RULES.set({"rules": dict(rules), "sizes": sizes, "mesh": mesh})
    try:
        yield
    finally:
        _RULES.reset(token)


def default_rules(mesh) -> dict:
    dp = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    return {
        "batch": dp,
        "heads": "model",
        "kv_heads": "model",
        "ffn": "model",
        "experts": "model",
        "vocab": "model",
        # Megatron-style sequence parallelism on the residual stream: the
        # per-layer saved activations shrink by |tp|; wire bytes equal the
        # pure-TP all-reduce (AR 2S == AG S + RS S).  Decode (s=1) demotes
        # to unconstrained automatically via the divisibility rule.
        "seq": "model",
    }


def constrain(x: jax.Array, *logical_axes) -> jax.Array:
    """Apply with_sharding_constraint following the bound rules.

    Entries: logical name (str), "*" (unconstrained), or None (replicated).
    Axes that do not divide the dimension are demoted to unconstrained —
    the paper's balance condition: only balanced forks are stolen.
    """
    ctx = _RULES.get()
    if ctx is None:
        return x
    rules, sizes = ctx["rules"], ctx["sizes"]
    if not sizes:
        return x
    entries = []
    for dim, name in zip(x.shape, logical_axes):
        if name == "*":
            entries.append(UNCONSTRAINED)
            continue
        if name is None:
            entries.append(None)
            continue
        axis = rules.get(name, "*")
        if axis == "*" or axis is None:
            entries.append(UNCONSTRAINED if axis == "*" else None)
            continue
        ax_tuple = axis if isinstance(axis, tuple) else (axis,)
        size = 1
        for a in ax_tuple:
            size *= sizes.get(a, 1)
        if size > 1 and dim % size == 0:
            entries.append(axis)
        else:
            entries.append(UNCONSTRAINED)
    if all(e is UNCONSTRAINED for e in entries):
        return x
    return jax.lax.with_sharding_constraint(x, P(*entries))


def mesh_axis(name, dim, multiple: int = 1):
    """The mesh axis logical ``name`` binds for an array dimension of size
    ``dim`` under the current rules; None (replicated) without a binding or
    where the axis does not split ``dim`` into blocks that are multiples of
    ``multiple``."""
    ctx = _RULES.get()
    return None if ctx is None else _mesh_axis(ctx, name, dim, multiple)


def _mesh_axis(ctx, name, dim, multiple: int = 1):
    if name is None:
        return None
    axis = ctx["rules"].get(name)
    if axis in (None, "*"):
        return None
    size = 1
    for a in (axis if isinstance(axis, tuple) else (axis,)):
        size *= ctx["sizes"].get(a, 1)
    return axis if size > 1 and dim % (size * multiple) == 0 else None


def kernel_map(fn, args, in_axes, out_axes, out_shape):
    """Call ``fn(*args)`` — a Pallas kernel launch returning one array of
    ``out_shape`` — once per shard of the bound mesh.  XLA cannot partition
    a Mosaic kernel itself, so under a mesh of more than one device the
    call runs inside ``jax.shard_map``: each argument is split along the
    mesh axes its logical ``in_axes`` (one entry per dimension, as in
    :func:`constrain`) bind, replicated where an axis does not divide, and
    ``fn`` sees its local block; the result is assembled along
    ``out_axes``.  ``fn`` must be correct on any such block: the axes it
    may split are ones its rows are independent along.  Without a
    multi-device binding this is a plain call."""
    ctx = _RULES.get()
    if ctx is None or ctx.get("mesh") is None or all(
            v == 1 for v in ctx["sizes"].values()):
        return fn(*args)
    in_specs = tuple(P(*(_mesh_axis(ctx, n, d)
                         for n, d in zip(axes, jnp.shape(x))))
                     for x, axes in zip(args, in_axes))
    out_specs = P(*(_mesh_axis(ctx, n, d)
                    for n, d in zip(out_axes, out_shape)))
    return jax.shard_map(fn, mesh=ctx["mesh"], in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(*args)
