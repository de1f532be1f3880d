"""A test-only family: a decoder whose MLP is routed experts (the
program's ``DenseLM`` with experts), tied to the harness through the
same functions a real family gives, with no reference forward.  The
harness's tests copy it into a benchmark root as ``reference/tiny_moe.py``.
"""
NORMS = ("ln1", "ln2", "q_norm", "k_norm", "final_norm")


def model_config(cfg: dict):
    from repro.configs import ModelConfig

    return ModelConfig(
        name=cfg["name"], family="moe", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], head_dim=cfg["head_dim"], qk_norm=True,
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        n_experts=cfg["num_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        expert_d_ff=cfg["moe_intermediate_size"], dtype=cfg["torch_dtype"])


def layout(cfg: dict):
    d, v, hd = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    e, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    top = {"embed": (v, d), "final_norm": (d,)}
    if not cfg["tie_word_embeddings"]:
        top["lm_head"] = (v, d)
    layer = {"ln1": (d,), "ln2": (d,), "wq": (d, q), "wk": (d, kv),
             "wv": (d, kv), "wo": (q, d), "q_norm": (hd,), "k_norm": (hd,),
             "router": ((d, e), "float32"), "e_gate": (e, d, f),
             "e_up": (e, d, f), "e_down": (e, f, d)}
    return top, {"layers": (range(cfg["num_hidden_layers"]), layer)}


def layer_matmul_params(cfg: dict) -> list[int]:
    """Attention, the router, and the experts a token is routed to."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    routed = cfg["num_experts_per_tok"] * 3 * d * cfg["moe_intermediate_size"]
    return ([d * (q + 2 * kv) + q * d + d * cfg["num_experts"] + routed]
            * cfg["num_hidden_layers"])


def layer_attention(cfg: dict) -> list[dict]:
    return [{"heads": cfg["num_attention_heads"],
             "kv_heads": cfg["num_key_value_heads"],
             "head_dim": cfg["head_dim"], "window": None}] * \
        cfg["num_hidden_layers"]


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]
