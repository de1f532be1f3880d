"""The plain reference against the program's model, and the seeded
weights both sides are given."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchroot
import spec
import weights

REF = spec.reference("qwen3")


def _program(cfg, seed):
    from repro.kernels import planner
    from repro.models import build_model
    from repro.models.base import RunOptions

    mc = REF.model_config(cfg)
    opts = planner.resolve_run_options(RunOptions(), head_dim=mc.head_dim_,
                                       dtype=mc.activation_dtype)
    model = build_model(mc, opts)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    return model, weights.program_params(REF, cfg, seed, shapes)


@pytest.mark.parametrize("tied", [True, False])
def test_reference_matches_prefill_through_cache_and_decode(tied):
    """Float32 on both sides: the program's first chunk, a continuation
    chunk through its LinearKV cache and per-row decode steps give the
    reference's logits at the same positions."""
    cfg = benchroot.tiny_config(torch_dtype="float32",
                                tie_word_embeddings=tied,
                                num_hidden_layers=2, vocab_size=512)
    seed, chunk, n_dec = 3, 16, 4
    model, params = _program(cfg, seed)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg["vocab_size"], 2 * chunk)
    served = rng.integers(0, cfg["vocab_size"], n_dec)
    cache = model.init_cache(1, 64)
    got = []
    with jax.default_matmul_precision("highest"):
        logits, cache = model.prefill_chunk(
            params, jnp.asarray(prompt[None, :chunk], jnp.int32),
            jnp.zeros((1,), jnp.int32), cache, first=True)
        logits, cache = model.prefill_chunk(
            params, jnp.asarray(prompt[None, chunk:], jnp.int32),
            jnp.full((1,), chunk, jnp.int32), cache, first=False)
        got.append(np.asarray(logits[0]))
        for j, tok in enumerate(served[:-1]):
            logits, cache = model.decode_step(
                params, jnp.asarray([[tok]], jnp.int32),
                jnp.asarray([2 * chunk + j], jnp.int32), cache)
            got.append(np.asarray(logits[0]))
        tokens = np.concatenate([prompt, served])
        rows = list(range(2 * chunk - 1, 2 * chunk - 1 + n_dec))
        want = np.asarray(REF.logits(
            tokens, rows, cfg, weights.reference_top(REF, cfg, seed),
            weights.reference_layer_fn(REF, cfg, seed)))
    got = np.stack(got)
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 1e-4 * scale, np.abs(got - want).max()


def test_reference_layers_are_the_programs_slices():
    cfg = benchroot.tiny_config(num_hidden_layers=3)
    seed = 2**40 + 7
    _, params = _program(cfg, seed)
    layer = weights.reference_layer_fn(REF, cfg, seed)
    top = weights.reference_top(REF, cfg, seed)
    for i in range(cfg["num_hidden_layers"]):
        ref = layer(i)
        for name, w in params["layers"].items():
            want = np.asarray(w[i], np.float32)
            if name in REF.NORMS:
                want = 1.0 + want
            np.testing.assert_array_equal(np.asarray(ref[name]), want)
    np.testing.assert_array_equal(np.asarray(top["embed"], np.float32),
                                  np.asarray(params["embed"], np.float32))


def test_weights_depend_on_the_whole_seed():
    cfg = benchroot.tiny_config(num_hidden_layers=1)
    a = weights.reference_layer_fn(REF, cfg, 5)(0)["wq"]
    b = weights.reference_layer_fn(REF, cfg, 5 + 2**32)(0)["wq"]
    assert not np.array_equal(np.asarray(a), np.asarray(b))


def test_program_layout_mismatch_raises():
    cfg = benchroot.tiny_config()
    _, params = _program(cfg, 0)
    shapes = jax.eval_shape(lambda: params)
    with pytest.raises(ValueError, match="layout"):
        weights.program_params(REF, dict(cfg, intermediate_size=256), 0,
                               shapes)


@pytest.mark.parametrize("quant,rel", [("int8", 0.5 / 127), ("fp8", 2.0**-4)])
def test_control_precisions_round_their_operands(quant, rel):
    """The control's rounding: each element within half a step of int8 at
    its row's scale, or within fp8 e4m3's relative spacing; never exact."""
    x = jnp.asarray(np.random.default_rng(1).standard_normal((8, 256)),
                    jnp.float32)
    r = REF._round(x, -1, quant)
    err = jnp.abs(r - x)
    amax = jnp.max(jnp.abs(x), -1, keepdims=True)
    if quant == "int8":
        assert bool(jnp.all(err <= rel * amax * 1.0001))
    else:
        assert bool(jnp.all(err <= rel * jnp.abs(x) + amax * 2.0**-9 / 448))
    assert float(err.max()) > 0
