"""Operations and bytes the model's work needs, from shapes alone.

Sizes come from the published config keys.  Every count is of useful work
(the tokens and context rows that requests own); padding that a launch
carries is not counted, so a share computed from these is a share of what
the user asked for.  Matmuls count 2 operations per multiply-add.
"""
from __future__ import annotations


def dims(cfg: dict) -> dict:
    h, kvh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    return {"d": cfg["hidden_size"], "ff": cfg["intermediate_size"],
            "v": cfg["vocab_size"], "q": h * hd, "kv": kvh * hd, "h": h,
            "kvh": kvh, "hd": hd, "layers": cfg["num_hidden_layers"]}


def layer_matmul_params(cfg: dict) -> int:
    """Weights one token multiplies through in one layer: q, k, v, o and the
    three MLP projections."""
    m = dims(cfg)
    return m["d"] * (m["q"] + 2 * m["kv"]) + m["q"] * m["d"] + 3 * m["d"] * m["ff"]


def attention_flops(cfg: dict, q_pos_from: int, q_pos_to: int) -> int:
    """Causal attention of query positions [from, to) over the positions up
    to each, all layers: q k and p v, 2 operations each per head dim."""
    m = dims(cfg)
    ctx = (q_pos_to * (q_pos_to + 1) - q_pos_from * (q_pos_from + 1)) // 2
    return 4 * m["h"] * m["hd"] * ctx * m["layers"]


def prefill_flops(cfg: dict, start: int, end: int, logits_rows: int = 1) -> int:
    """A request's prompt positions [start, end) through every layer, plus
    the logits of ``logits_rows`` rows (a chunk reads its last row)."""
    m = dims(cfg)
    return (2 * layer_matmul_params(cfg) * m["layers"] * (end - start)
            + attention_flops(cfg, start, end)
            + 2 * m["d"] * m["v"] * logits_rows)


def decode_flops(cfg: dict, kv_len: int) -> int:
    """One decoded token whose query attends ``kv_len`` positions."""
    m = dims(cfg)
    return (2 * layer_matmul_params(cfg) * m["layers"]
            + 4 * m["h"] * m["hd"] * kv_len * m["layers"]
            + 2 * m["d"] * m["v"])


def attn_decode_call(cfg: dict, kv_lens, kv_bytes: int = 2) -> tuple[int, int]:
    """(operations, bytes) of one layer's decode-attention call for rows
    attending ``kv_lens`` positions each: every live key and value row is
    read once; the query and output rows are read and written once."""
    m = dims(cfg)
    live = sum(kv_lens)
    flops = 4 * m["h"] * m["hd"] * live
    nbytes = (2 * live * m["kvh"] * m["hd"] * kv_bytes
              + 2 * len(kv_lens) * m["q"] * 2)
    return flops, nbytes


def matmul(m_rows: int, k: int, n: int, itemsize: int = 2) -> tuple[int, int]:
    """(operations, bytes) of one (m, k) @ (k, n) product: both operands
    read and the result written once."""
    return 2 * m_rows * k * n, (m_rows * k + k * n + m_rows * n) * itemsize


def least_time_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time a chip with these peaks needs: the larger of the
    compute bound and the memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
