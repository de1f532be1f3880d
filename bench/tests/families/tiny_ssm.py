"""A test-only family: an attention-free state-space decoder (the
program's ``SSMLM``, Mamba-2 layers), tied to the harness through the same
functions a real family gives, with no reference forward.  The harness's
tests copy it into a benchmark root as ``reference/tiny_ssm.py``.
"""
NORMS = ("ln", "gn", "final_norm")


def model_config(cfg: dict):
    from repro.configs import ModelConfig

    return ModelConfig(
        name=cfg["name"], family="ssm", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=0, n_kv_heads=0, d_ff=0,
        vocab_size=cfg["vocab_size"], norm_eps=cfg["layer_norm_epsilon"],
        ssm_state=cfg["state_size"], ssm_expand=cfg["expand"],
        ssm_head_dim=cfg["head_dim"], ssm_chunk=cfg["chunk_size"],
        conv1d_width=cfg["conv_kernel"], dtype=cfg["torch_dtype"])


def _inner(cfg: dict) -> tuple[int, int, int]:
    di = cfg["expand"] * cfg["hidden_size"]
    return di, cfg["state_size"], di // cfg["head_dim"]


def layout(cfg: dict):
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    di, ds, nh = _inner(cfg)
    layer = {"ln": (d,), "w_in": (d, 2 * di + 2 * ds + nh),
             "conv_w": (cfg["conv_kernel"], di + 2 * ds),
             "A_log": ((nh,), "float32"), "dt_bias": ((nh,), "float32"),
             "D": ((nh,), "float32"), "gn": (di,), "w_out": (di, d)}
    return ({"embed": (v, d), "final_norm": (d,)},
            {"layers": (range(cfg["num_hidden_layers"]), layer)})


def layer_matmul_params(cfg: dict) -> list[int]:
    """The input projection (z, x, B, C, dt) and the output projection."""
    d = cfg["hidden_size"]
    di, ds, nh = _inner(cfg)
    return [d * (2 * di + 2 * ds + nh) + di * d] * cfg["num_hidden_layers"]


def layer_attention(cfg: dict) -> list:
    return [None] * cfg["num_hidden_layers"]


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]
