"""Operations and bytes the model's work needs, from shapes alone.

What one token multiplies through, and how each layer attends, is the
model family's own: its reference module ``ref`` gives, from the config,
``layer_matmul_params(cfg)`` (per layer, the matmul weights one token
multiplies through: for experts, only those it is routed to, and the
router), ``layer_attention(cfg)`` (per layer, ``None`` where it does not
attend, else its ``heads``, ``kv_heads``, ``head_dim`` and ``window``,
``None`` for every earlier position) and ``head_params(cfg)`` (the weights
a row whose logits are read multiplies through).  Every count is of useful
work (the tokens and context rows that requests own); padding that a
launch carries is not counted, so a share computed from these is a share
of what the user asked for.  Matmuls count 2 operations per multiply-add.
"""
from __future__ import annotations


def _keys(n: int, window: int | None) -> int:
    """Keys the first ``n`` query positions attend together, each the
    positions up to its own, at most ``window`` of them."""
    if window is None or n <= window:
        return n * (n + 1) // 2
    return window * (window + 1) // 2 + (n - window) * window


def attention_flops(ref, cfg: dict, q_pos_from: int, q_pos_to: int) -> int:
    """Causal attention of query positions [from, to) over the keys each
    attends, all attending layers: q k and p v, 2 operations each per head
    dim."""
    return sum(4 * a["heads"] * a["head_dim"]
               * (_keys(q_pos_to, a["window"])
                  - _keys(q_pos_from, a["window"]))
               for a in ref.layer_attention(cfg) if a is not None)


def prefill_flops(ref, cfg: dict, start: int, end: int,
                  logits_rows: int = 1) -> int:
    """A request's prompt positions [start, end) through every layer, plus
    the logits of ``logits_rows`` rows (a chunk reads its last row)."""
    return (2 * sum(ref.layer_matmul_params(cfg)) * (end - start)
            + attention_flops(ref, cfg, start, end)
            + 2 * ref.head_params(cfg) * logits_rows)


def decode_flops(ref, cfg: dict, kv_len: int) -> int:
    """One decoded token whose query attends ``kv_len`` positions."""
    return prefill_flops(ref, cfg, kv_len - 1, kv_len)


def attn_decode_call(ref, cfg: dict, kv_lens,
                     kv_bytes: int = 2) -> tuple[int, int] | None:
    """(operations, bytes) of one layer's decode-attention call, averaged
    over the attending layers, for rows attending ``kv_lens`` positions
    each (a windowed layer at most its window): every live key and value
    row is read once; the query and output rows are read and written
    once.  None where no layer attends."""
    layers = [a for a in ref.layer_attention(cfg) if a is not None]
    if not layers:
        return None
    flops = nbytes = 0
    for a in layers:
        live = sum(n if a["window"] is None else min(n, a["window"])
                   for n in kv_lens)
        q = a["heads"] * a["head_dim"]
        flops += 4 * q * live
        nbytes += (2 * live * a["kv_heads"] * a["head_dim"] * kv_bytes
                   + 2 * len(kv_lens) * q * 2)
    return flops // len(layers), nbytes // len(layers)


def matmul(m_rows: int, k: int, n: int, itemsize: int = 2) -> tuple[int, int]:
    """(operations, bytes) of one (m, k) @ (k, n) product: both operands
    read and the result written once."""
    return 2 * m_rows * k * n, (m_rows * k + k * n + m_rows * n) * itemsize


def least_time_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time a chip with these peaks needs: the larger of the
    compute bound and the memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
