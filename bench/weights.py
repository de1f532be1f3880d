"""Weights from the seed, made on the device, for the program and the
reference alike.

Each weight is a function of (seed, leaf name, layer index) alone: 16 random
bits per element, centred and scaled by one float32 multiply, then rounded
to the served dtype.  Only integer operations and one correctly rounded
multiply are involved, so a layer generated alone (for the reference) is
bit for bit the slice of the stacked tree generated for the program.

Which leaves there are, and their shapes, is the model family's own: its
reference module (``reference/<model_type>.py``) gives ``layout(cfg)`` and
the names of its norm leaves, ``NORMS``.  Other leaves are uniform with
the standard deviation of the config's ``initializer_range``.  Norm leaves
hold an offset: the program's RMSNorm scales by ``1 + w`` and stores
``w``; the published model scales by ``g``, so the reference receives
``g = 1 + w``.
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


def program_params(ref, cfg: dict, seed: int, program_shapes,
                   shardings=None):
    """The program's parameter tree (``{"embed", "final_norm", ["lm_head"],
    "layers": {name: (L, ...)}}``) made in one jitted call on the device.
    ``program_shapes`` is the program's own abstract tree; it must hold
    exactly the leaves of the reference module ``ref``'s ``layout``, or the
    weights could not mean the same model to both sides."""
    top, layer = ref.layout(cfg)
    n_layers = cfg["num_hidden_layers"]
    dtype = jnp.dtype(cfg["torch_dtype"])
    want = {**{k: (s, dtype) for k, s in top.items()},
            **{f"layers/{k}": ((n_layers, *s), dtype)
               for k, s in layer.items()}}
    flat = jax.tree_util.tree_flatten_with_path(program_shapes)[0]
    have = {"/".join(str(getattr(p, "key", p)) for p in path):
            (tuple(a.shape), jnp.dtype(a.dtype)) for path, a in flat}
    if have != want:
        raise ValueError(f"the program's parameters {sorted(have.items())} "
                         f"are not the layout {sorted(want.items())}")

    def make(key):
        out = {k: leaf(key, k, 0, s, dtype, _std(ref, cfg, k))
               for k, s in top.items()}
        out["layers"] = {
            k: jax.vmap(lambda i, k=k, s=s: leaf(key, k, i, s, dtype,
                                                 _std(ref, cfg, k)))(
                jnp.arange(n_layers))
            for k, s in layer.items()}
        return out

    return jax.jit(make, out_shardings=shardings)(seed_key(seed))


def _published(ref, name: str, w):
    w = w.astype(jnp.float32)
    return 1.0 + w if name in ref.NORMS else w


def reference_top(ref, cfg: dict, seed: int) -> dict:
    """Whole-model leaves for the reference, in the served dtype (the
    reference widens them where it uses them); norms as published scales
    in float32."""
    top, _ = ref.layout(cfg)
    dtype = jnp.dtype(cfg["torch_dtype"])

    def make(key):
        out = {k: leaf(key, k, 0, s, dtype, _std(ref, cfg, k))
               for k, s in top.items()}
        return {k: _published(ref, k, w) if k in ref.NORMS else w
                for k, w in out.items()}

    return jax.jit(make)(seed_key(seed))


def reference_layer_fn(ref, cfg: dict, seed: int):
    """``fn(i)`` -> layer ``i``'s leaves in float32, norms as published
    scales; compiled once for every layer."""
    _, layer = ref.layout(cfg)
    dtype = jnp.dtype(cfg["torch_dtype"])
    key = seed_key(seed)

    @jax.jit
    def make(key, i):
        return {k: _published(ref, k, leaf(key, k, i, s, dtype,
                                           _std(ref, cfg, k)))
                for k, s in layer.items()}

    return lambda i: make(key, jnp.int32(i))
