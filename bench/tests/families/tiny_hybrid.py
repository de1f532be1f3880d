"""A test-only family: recurrent (RG-LRU) blocks and windowed attention in
a repeating pattern (the program's ``HybridLM``), whose weights sit in
four stacks, tied to the harness through the same functions a real family
gives, with no reference forward.  The harness's tests copy it into a
benchmark root as ``reference/tiny_hybrid.py``.
"""
NORMS = ("ln1", "ln2", "final_norm")
PATTERN = ("recurrent", "recurrent", "attention")


def model_config(cfg: dict):
    from repro.configs import ModelConfig

    if tuple(cfg["block_types"]) != PATTERN:
        raise ValueError(f"the program's pattern is {PATTERN}")
    return ModelConfig(
        name=cfg["name"], family="hybrid", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], head_dim=cfg["head_dim"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        sliding_window=cfg["attention_window_size"],
        block_pattern=("rglru", "rglru", "attn"),
        lru_width=cfg["lru_width"], conv1d_width=cfg["conv1d_width"],
        dtype=cfg["torch_dtype"])


def _kinds(cfg: dict) -> list[str]:
    n = cfg["num_hidden_layers"]
    return [PATTERN[i % len(PATTERN)] for i in range(n)]


def layout(cfg: dict):
    d, v, ff = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    w, nh, hd = cfg["lru_width"], cfg["num_attention_heads"], cfg["head_dim"]
    mlp = {"w_mlp_gate": (d, ff), "w_mlp_up": (d, ff), "w_mlp_down": (ff, d)}
    rec = {"ln1": (d,), "ln2": (d,), "w_x": (d, w), "w_gate_branch": (d, w),
           "conv_w": (cfg["conv1d_width"], w),
           "lru_a_gate": ((nh, w // nh, w // nh), "float32"),
           "lru_i_gate": ((nh, w // nh, w // nh), "float32"),
           "lru_a_param": ((w,), "float32"), "w_out": (w, d), **mlp}
    attn = {"ln1": (d,), "ln2": (d,), "wq": (d, nh * hd),
            "wk": (d, cfg["num_key_value_heads"] * hd),
            "wv": (d, cfg["num_key_value_heads"] * hd), "wo": (nh * hd, d),
            **mlp}
    whole = cfg["num_hidden_layers"] // len(PATTERN) * len(PATTERN)
    stacks = {"groups/rec1": (range(0, whole, 3), rec),
              "groups/rec2": (range(1, whole, 3), rec),
              "groups/attn": (range(2, whole, 3), attn),
              "tail_rec": (range(whole, cfg["num_hidden_layers"]), rec)}
    return {"embed": (v, d), "final_norm": (d,)}, stacks


def layer_matmul_params(cfg: dict) -> list[int]:
    """A recurrent layer: its two input branches, the two block-diagonal
    gates, its output and the MLP; an attention layer: q, k, v, o and the
    MLP."""
    d, ff, w = cfg["hidden_size"], cfg["intermediate_size"], cfg["lru_width"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    rec = 2 * d * w + 2 * w * (w // cfg["num_attention_heads"]) + w * d
    attn = d * (q + 2 * kv) + q * d
    return [(attn if k == "attention" else rec) + 3 * d * ff
            for k in _kinds(cfg)]


def layer_attention(cfg: dict) -> list:
    a = {"heads": cfg["num_attention_heads"],
         "kv_heads": cfg["num_key_value_heads"], "head_dim": cfg["head_dim"],
         "window": cfg["attention_window_size"]}
    return [a if k == "attention" else None for k in _kinds(cfg)]


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]
