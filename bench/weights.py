"""Weights from the seed, made on the device, for the program and the
reference alike.

Each weight is a function of (seed, leaf name, layer index) alone: 16 random
bits per element, centred and scaled by one float32 multiply, then rounded
to the served dtype.  Only integer operations and one correctly rounded
multiply are involved, so a layer generated alone (for the reference) is
bit for bit the slice of the stacked tree generated for the program.

Which leaves there are, their shapes and dtypes, is the model family's
own: its reference module (``reference/<model_type>.py``) gives
``layout(cfg)`` and the names of its norm leaves, ``NORMS``.  The layout
is ``(top, stacks)``: ``top`` maps a whole-model leaf's name to its shape;
``stacks`` maps the program's path of a stack of layers (``"layers"``,
``"groups/attn"``) to ``(layers, leaves)``, the model's indices of the
layers it holds, in order, and each leaf's shape in one layer.  A shape is
in the served dtype (``torch_dtype``); a ``(shape, dtype)`` pair gives a
leaf its own.  A stacked leaf is a function of its name and its layer's
model index, whichever stack holds it.

Leaves are uniform with the standard deviation of the config's
``initializer_range``, norm leaves with ``NORM_STD``.  Norm leaves hold an
offset: the program's RMSNorm scales by ``1 + w`` and stores ``w``; the
published model scales by ``g``, so the reference receives ``g = 1 + w``.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

NORM_STD = 0.1


def seed_key(seed: int):
    """A PRNG key from any whole seed (JAX keeps only 32 bits of an int)."""
    return jax.random.key(int(np.random.SeedSequence(seed).generate_state(1)[0]))


def leaf(key, name: str, layer, shape, dtype, std: float):
    k = jax.random.fold_in(jax.random.fold_in(key, zlib.crc32(name.encode())
                                              & 0x7FFFFFFF), layer)
    bits = jax.random.bits(k, tuple(shape), jnp.uint16)
    step = np.float32(std * math.sqrt(3.0) / 32768.0)
    centred = (bits.astype(jnp.int32) - 32768).astype(jnp.float32)
    return (centred * step).astype(dtype)


def _std(ref, cfg: dict, name: str) -> float:
    return NORM_STD if name in ref.NORMS else float(cfg["initializer_range"])


def _shape_dtype(spec, served) -> tuple:
    """A layout entry as (shape, dtype): a shape is in the served dtype."""
    if len(spec) == 2 and isinstance(spec[1], str):
        return tuple(spec[0]), jnp.dtype(spec[1])
    return tuple(spec), served


def _layout(ref, cfg: dict) -> tuple[dict, dict]:
    """The family's layout with every leaf as (shape, dtype) and every
    stack's layers as a tuple."""
    top, stacks = ref.layout(cfg)
    served = jnp.dtype(cfg["torch_dtype"])
    top = {k: _shape_dtype(s, served) for k, s in top.items()}
    stacks = {path: (tuple(layers), {k: _shape_dtype(s, served)
                                     for k, s in leaves.items()})
              for path, (layers, leaves) in stacks.items()}
    return top, stacks


def _nest(tree: dict, path: str, value) -> None:
    *outer, last = path.split("/")
    for k in outer:
        tree = tree.setdefault(k, {})
    tree[last] = value


def program_params(ref, cfg: dict, seed: int, program_shapes,
                   shardings=None):
    """The program's parameter tree (whole-model leaves and each stack of
    layers at its path, every stacked leaf ``(layers, ...)``) made in one
    jitted call on the device.  ``program_shapes`` is the program's own
    abstract tree; it must hold exactly the leaves of the reference module
    ``ref``'s ``layout``, or the weights could not mean the same model to
    both sides."""
    top, stacks = _layout(ref, cfg)
    want = {**top,
            **{f"{path}/{k}": ((len(layers), *s), dt)
               for path, (layers, leaves) in stacks.items()
               for k, (s, dt) in leaves.items()}}
    flat = jax.tree_util.tree_flatten_with_path(program_shapes)[0]
    have = {"/".join(str(getattr(p, "key", p)) for p in path):
            (tuple(a.shape), jnp.dtype(a.dtype)) for path, a in flat}
    if have != want:
        raise ValueError(f"the program's parameters {sorted(have.items())} "
                         f"are not the layout {sorted(want.items())}")

    def make(key):
        out = {k: leaf(key, k, 0, s, dt, _std(ref, cfg, k))
               for k, (s, dt) in top.items()}
        for path, (layers, leaves) in stacks.items():
            _nest(out, path, {
                k: jax.vmap(lambda i, k=k, s=s, dt=dt: leaf(
                    key, k, i, s, dt, _std(ref, cfg, k)))(
                    jnp.asarray(layers, jnp.int32))
                for k, (s, dt) in leaves.items()})
        return out

    return jax.jit(make, out_shardings=shardings)(seed_key(seed))


def _published(ref, name: str, w):
    w = w.astype(jnp.float32)
    return 1.0 + w if name in ref.NORMS else w


def reference_top(ref, cfg: dict, seed: int) -> dict:
    """Whole-model leaves for the reference, each in its layout dtype (the
    reference widens them where it uses them); norms as published scales
    in float32."""
    top, _ = _layout(ref, cfg)

    def make(key):
        out = {k: leaf(key, k, 0, s, dt, _std(ref, cfg, k))
               for k, (s, dt) in top.items()}
        return {k: _published(ref, k, w) if k in ref.NORMS else w
                for k, w in out.items()}

    return jax.jit(make)(seed_key(seed))


def _layer_maker(ref, cfg: dict, leaves: dict):
    @jax.jit
    def make(key, i):
        return {k: _published(ref, k, leaf(key, k, i, s, dt,
                                           _std(ref, cfg, k)))
                for k, (s, dt) in leaves.items()}

    return make


def reference_layer_fn(ref, cfg: dict, seed: int):
    """``fn(i)`` -> the leaves of the model's layer ``i``, from whichever
    stack holds it, in float32, norms as published scales; compiled once
    for each stack."""
    _, stacks = _layout(ref, cfg)
    key = seed_key(seed)
    maker = {}
    for layers, leaves in stacks.values():
        make = _layer_maker(ref, cfg, leaves)
        maker.update(dict.fromkeys(layers, make))
    return lambda i: maker[i](key, jnp.int32(i))
