"""Plain float32 reference of the Qwen3 dense decoder.

Follows the published description (Hugging Face ``modeling_qwen3``):
pre-norm residual blocks; RMSNorm scaling by its weight; q and k each
RMS-normalised per head (``q_norm``/``k_norm``) before rotary embedding
(rotate-half, base ``rope_theta``); grouped-query attention, query head
``h`` reading key/value head ``h // (heads / kv_heads)``; softmax over
``q k / sqrt(head_dim)`` under a causal mask; SwiGLU MLP
``down(silu(gate(x)) * up(x))``; a final norm and the head, tied to the
embedding or not.  No kernels, no cache, no batching: one sequence at a
time, every position recomputed, float32 throughout, matmuls at
``highest`` precision (set by the caller).

Weights come in as arguments, with the leaves and shapes of
:func:`layout`: ``top`` holds ``embed`` (V, d), ``final_norm`` and, untied,
``lm_head`` (V, d); ``layer(i)`` returns layer ``i``'s leaves, matrices laid
out (in, out).  Norm weights (``NORMS``) are the published scales.

Beside the reference, the module ties the family to the harness: the
program's ``ModelConfig`` (:func:`model_config`), the weights' layout, and
the counts the operation metrics read (:func:`layer_matmul_params`,
:func:`layer_attention`, :func:`head_params`), all from the published keys.

``quant`` selects the control: the same model with every projection, MLP
and head matmul taking its operands rounded to a lower precision
(``"int8"``: symmetric int8, per row of activations and per output column
of weights; ``"fp8"``: float8 e4m3 with the same scaling).  Attention
stays float32 in both.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NORMS = ("ln1", "ln2", "q_norm", "k_norm", "final_norm")


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a config file, every field from
    its published keys: the program's dense decoder with q/k norms."""
    from repro.configs import ModelConfig

    if cfg["use_sliding_window"]:
        raise ValueError(f"{cfg['name']}: the Qwen3 reference attends every "
                         "position; a sliding window is not modelled")
    return ModelConfig(
        name=cfg["name"], family="dense", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], head_dim=cfg["head_dim"],
        qkv_bias=cfg["attention_bias"], qk_norm=True,
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"], dtype=cfg["torch_dtype"])


def layout(cfg: dict) -> tuple[dict, dict]:
    """(whole-model leaves, stacks of layers) from the published config
    keys: the weights this family's model holds.  Leaves map name ->
    shape, in the served dtype; the one stack, ``layers``, holds every
    layer in order."""
    d, ff, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    h, kvh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    top = {"embed": (v, d), "final_norm": (d,)}
    if not cfg["tie_word_embeddings"]:
        top["lm_head"] = (v, d)
    layer = {"ln1": (d,), "ln2": (d,), "wq": (d, h * hd), "wk": (d, kvh * hd),
             "wv": (d, kvh * hd), "wo": (h * hd, d), "q_norm": (hd,),
             "k_norm": (hd,), "w_gate": (d, ff), "w_up": (d, ff),
             "w_down": (ff, d)}
    return top, {"layers": (range(cfg["num_hidden_layers"]), layer)}


def layer_matmul_params(cfg: dict) -> list[int]:
    """Per layer, the weights one token multiplies through: q, k, v, o and
    the three MLP projections."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return [d * (q + 2 * kv) + q * d + 3 * d * ff] * cfg["num_hidden_layers"]


def layer_attention(cfg: dict) -> list[dict]:
    """Per layer, its attention: every layer attends every earlier
    position."""
    a = {"heads": cfg["num_attention_heads"],
         "kv_heads": cfg["num_key_value_heads"], "head_dim": cfg["head_dim"],
         "window": None}
    return [a] * cfg["num_hidden_layers"]


def head_params(cfg: dict) -> int:
    """Weights a row whose logits are read multiplies through in the head."""
    return cfg["hidden_size"] * cfg["vocab_size"]


Q_BLOCK = 512   # query rows per attention block (bounds the score tensor)
SEQ_BUCKET = 512  # sequences pad to a multiple of this: few compiled shapes
ROW_BUCKET = 128  # and the rows whose logits are read, to a multiple of this


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, pos, theta):
    """x: (T, heads, hd); rotate-half rotary embedding at positions pos."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    rot = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], -1)
    return x * cos + rot * sin


def _round(x, axis, quant):
    """Round ``x`` to ``quant`` with one symmetric scale per slice along
    ``axis`` (the contraction axis), back in float32."""
    top = {"int8": 127.0, "fp8": 448.0}[quant]
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top,
                    1e-30)
    if quant == "int8":
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(x, w, quant=None):
    """x (..., k) @ w (k, n)."""
    if quant is not None:
        x, w = _round(x, -1, quant), _round(w, 0, quant)
    return x @ w


def attention(q, k, v):
    """Causal softmax attention; q (T, H, hd), k/v (T, KVH, hd)."""
    t, h, hd = q.shape
    rep = h // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    outs = []
    for start in range(0, t, Q_BLOCK):
        end = min(start + Q_BLOCK, t)
        s = jnp.einsum("qhd,khd->hqk", q[start:end], k[:end]) / jnp.sqrt(
            jnp.float32(hd))
        qp = jnp.arange(start, end)[:, None]
        kp = jnp.arange(end)[None, :]
        s = jnp.where(kp <= qp, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v[:end]))
    return jnp.concatenate(outs, 0)


@functools.partial(jax.jit, static_argnames=("cfg", "quant"))
def layer_forward(x, p, cfg, quant=None):
    """One decoder layer over a whole sequence x (T, d)."""
    cfg = dict(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    t = x.shape[0]
    h, kvh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    pos = jnp.arange(t)
    a = rms_norm(x, p["ln1"], eps)
    q = mm(a, p["wq"], quant).reshape(t, h, hd)
    k = mm(a, p["wk"], quant).reshape(t, kvh, hd)
    v = mm(a, p["wv"], quant).reshape(t, kvh, hd)
    q = rope(rms_norm(q, p["q_norm"], eps), pos, theta)
    k = rope(rms_norm(k, p["k_norm"], eps), pos, theta)
    o = attention(q, k, v).reshape(t, h * hd)
    x = x + mm(o, p["wo"], quant)
    m = rms_norm(x, p["ln2"], eps)
    g = jax.nn.silu(mm(m, p["w_gate"], quant)) * mm(m, p["w_up"], quant)
    return x + mm(g, p["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("cfg", "quant"))
def head(x, final_norm, out_embed, cfg, quant=None):
    """Logits (N, V) of hidden rows x (N, d)."""
    cfg = dict(cfg)
    h = rms_norm(x, final_norm, cfg["rms_norm_eps"])
    return mm(h, out_embed.astype(jnp.float32).T, quant)


def static_cfg(cfg: dict) -> tuple:
    """The config's sizes as a hashable jit argument."""
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rms_norm_eps", "rope_theta")
    return tuple((k, cfg[k]) for k in keys)


def logits(tokens, rows, cfg: dict, top: dict, layer, quant=None):
    """Logits (len(rows), V) at positions ``rows`` of the sequence
    ``tokens``, run layer by layer.  The sequence is padded at its end to a
    multiple of ``SEQ_BUCKET``; under the causal mask padding never reaches
    an earlier position."""
    n = len(tokens)
    t = -(-n // SEQ_BUCKET) * SEQ_BUCKET
    toks = jnp.zeros((t,), jnp.int32).at[:n].set(jnp.asarray(tokens))
    x = top["embed"][toks].astype(jnp.float32)
    sc = static_cfg(cfg)
    for i in range(cfg["num_hidden_layers"]):
        x = layer_forward(x, layer(i), cfg=sc, quant=quant)
    out = top["embed"] if cfg["tie_word_embeddings"] else top["lm_head"]
    # rows pad to a multiple of ROW_BUCKET (repeating the last) for the same
    # reason the sequence does
    r = len(rows)
    padded = list(rows) + [rows[-1]] * (-r % ROW_BUCKET)
    return head(x[jnp.asarray(padded)], top["final_norm"], out, cfg=sc,
                quant=quant)[:r]
