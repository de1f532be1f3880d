"""A model family's whole tie to the harness is its module,
``reference/<model_type>.py``.  The Qwen3 cells get the program, the
weights and the operation counts they got before the harness read them
from that module; a family with another layout is served by adding its
config and its module, and editing nothing; a family with no module is
refused before any device work."""
import dataclasses
import hashlib
import json
import shutil
import time
from pathlib import Path

import jax
import numpy as np
import pytest

import benchroot
import flops
import run
import serving
import spec
import traffic
import weights

REF = spec.reference("qwen3")
FAMILIES = Path(__file__).resolve().parent / "families"


def _registered_model_config(cfg):
    """The ``ModelConfig`` the harness built before the family module made
    it: the architecture registered as ``arch``, with the published sizes
    put in."""
    from repro.configs import get_config

    return dataclasses.replace(
        get_config(cfg["arch"]), name=cfg["name"],
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], head_dim=cfg["head_dim"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        qkv_bias=cfg["attention_bias"], dtype=cfg["torch_dtype"])


@pytest.mark.parametrize("name", ["qwen3-1.7b", "qwen3-32b-l6"])
def test_model_config_is_the_registered_one(name):
    cfg = spec.config(name)
    got, want = REF.model_config(cfg), _registered_model_config(cfg)
    for f in dataclasses.fields(want):
        assert getattr(got, f.name) == getattr(want, f.name), f.name


# per-layer matmul weights, prefill_flops(0, 256), decode_flops(1024),
# attn_decode_call([512, 1024]), as the harness counted them from the
# dense keys before the family module gave the counts
COUNTS = {
    "qwen3-1.7b": (50_331_648, 729_722_388_480, 3_675_783_168,
                   (12_582_912, 6_307_840)),
    "qwen3-32b-l6": (487_587_840, 1_505_893_285_888, 7_608_205_312,
                     (50_331_648, 6_356_992)),
}


@pytest.mark.parametrize("name", sorted(COUNTS))
def test_operation_counts_are_unchanged(name):
    cfg = spec.config(name)
    per_layer, prefill, decode, attn = COUNTS[name]
    assert REF.layer_matmul_params(cfg) == [per_layer] * cfg[
        "num_hidden_layers"]
    assert flops.prefill_flops(REF, cfg, 0, 256) == prefill
    assert flops.decode_flops(REF, cfg, 1024) == decode
    assert flops.attn_decode_call(REF, cfg, [512, 1024]) == attn


def _digest(leaves: dict) -> str:
    h = hashlib.sha256()
    for k in sorted(leaves):
        h.update(k.encode())
        h.update(np.asarray(leaves[k]).tobytes())
    return h.hexdigest()


# sha256 of the program's tree and of the reference's layers and
# whole-model leaves, each leaf by its path, at Qwen3's tiny widths with
# three layers, seed 2**40 + 7, as the harness made them from a layout of
# one stack in one dtype
WEIGHT_DIGESTS = {
    "qwen3-1.7b": (
        "19b20d61e60551063c17741da036a3088f8df86ad6fd6c65875d7dd8a730f098",
        "327cb9a61fe4c2b2c23c03b8e049410d4bfe9a61e8af04c3b665d42d4c4ef8b5"),
    "qwen3-32b-l6": (
        "0457b84ca63dd2fa26dbffcd21af5f0b86d662678e00b2ff648ae41be388c805",
        "30cf452de85d63c05fc7141b8f2091c8d344037d29a3f3c300176e89d545658b"),
}


@pytest.mark.parametrize("name", sorted(WEIGHT_DIGESTS))
def test_weights_are_unchanged_bit_for_bit(name):
    from repro.models import build_model

    cfg = dict(spec.config(name), **dict(benchroot.TINY, num_hidden_layers=3))
    seed = 2**40 + 7
    shapes = jax.eval_shape(build_model(REF.model_config(cfg)).init,
                            jax.random.key(0))
    params = weights.program_params(REF, cfg, seed, shapes)
    program = {"/".join(k.key for k in path): a for path, a in
               jax.tree_util.tree_flatten_with_path(params)[0]}
    layer = weights.reference_layer_fn(REF, cfg, seed)
    # the reference's digest chains each layer's leaves, then the top's
    ref = hashlib.sha256()
    for i in range(cfg["num_hidden_layers"]):
        for k, a in sorted(layer(i).items()):
            ref.update(k.encode())
            ref.update(np.asarray(a).tobytes())
    for k, a in sorted(weights.reference_top(REF, cfg, seed).items()):
        ref.update(k.encode())
        ref.update(np.asarray(a).tobytes())
    assert (_digest(program), ref.hexdigest()) == WEIGHT_DIGESTS[name]


# a backlog of short requests that fit the tiny families' 64 positions
BACKLOG = {"arrivals": {"process": "backlog", "waiting_per_slot": 1},
           "block": 8,
           "prompt": {"dist": "uniform", "min": 8, "max": 40},
           "output": {"dist": "uniform", "min": 3, "max": 10}}

# prefill_flops(0, 16), decode_flops(20), attn_decode_call([4, 20]), worked
# out by hand from each tiny config
FAMILY_COUNTS = {
    # 2 layers: q, k, v, o (12,288), the router (64 x 8) and the 2 experts
    # a token is routed to (2 x 3 x 64 x 32), never all 8; full attention
    "moe": (2 * 16 * 2 * (12_288 + 512 + 2 * 3 * 64 * 32)
            + 4 * 64 * 136 * 2 + 2 * 64 * 256,
            2 * 2 * (12_288 + 512 + 2 * 3 * 64 * 32)
            + 4 * 64 * 20 * 2 + 2 * 64 * 256,
            (4 * 64 * 24, 2 * 24 * 2 * 16 * 2 + 2 * 2 * 64 * 2)),
    # 4 recurrent layers (40,960 each with the MLP) and 1 attention layer
    # (36,864) whose window is 8: 16 queries attend 36 + 8 x 8 keys
    "hybrid": (2 * 16 * (4 * 40_960 + 36_864) + 4 * 64 * 100
               + 2 * 64 * 256,
               2 * (4 * 40_960 + 36_864) + 4 * 64 * 8 + 2 * 64 * 256,
               (4 * 64 * 12, 2 * 12 * 32 * 2 + 2 * 2 * 64 * 2)),
    # 2 layers of in and out projections (18,944 + 8,192); nothing attends
    "ssm": (2 * 16 * 2 * 27_136 + 2 * 64 * 256,
            2 * 2 * 27_136 + 2 * 64 * 256,
            None),
}


def _bench_with(root: Path, family: str) -> tuple[dict, object]:
    """A copy of the benchmark's harness with one family added: its config
    and its module, as new files; no file of the copy is edited."""
    shutil.copytree(benchroot.BENCH, root,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    name = f"tiny-{family}"
    shutil.copy(FAMILIES / f"tiny_{family}.json",
                root / "configs" / f"{name}.json")
    shutil.copy(FAMILIES / f"tiny_{family}.py",
                root / "reference" / f"tiny_{family}.py")
    cfg = spec.config(name, root)
    return cfg, spec.reference(cfg["model_type"], root)


@pytest.mark.parametrize("family", sorted(FAMILY_COUNTS))
def test_a_family_is_served_by_adding_files(tmp_path, family):
    cfg, ref = _bench_with(tmp_path / "bench", family)
    seed = 2**33 + 5
    engine = serving.build_engine(cfg, seed, ref)

    # every stack's layers, alone for the reference, are the program's
    # slices, each in its layout's dtype
    layer = weights.reference_layer_fn(ref, cfg, seed)
    _, stacks = ref.layout(cfg)
    for path, (layers, leaves) in stacks.items():
        tree = engine.params
        for k in path.split("/"):
            tree = tree[k]
        for j, i in enumerate(layers):
            alone = layer(i)
            for k in leaves:
                want = np.asarray(tree[k][j], np.float32)
                if k in ref.NORMS:
                    want = 1.0 + want
                np.testing.assert_array_equal(np.asarray(alone[k]), want)

    engine.compile()
    stream = traffic.Stream(BACKLOG, seed, cfg["vocab_size"], None,
                            tmp_path / "bench")
    served = serving.drive(engine, stream, warmup_s=0.5, seconds=2.0,
                           drain_max_s=120)
    tracked = served.tracked(open_loop=False)
    assert tracked
    for r in tracked:
        assert r.done is not None and len(r.req.out) == r.max_new
        assert all(0 <= t < cfg["vocab_size"] for t in r.req.out)

    prefill, decode, attn = FAMILY_COUNTS[family]
    assert flops.prefill_flops(ref, cfg, 0, 16) == prefill
    assert flops.decode_flops(ref, cfg, 20) == decode
    assert flops.attn_decode_call(ref, cfg, [4, 20]) == attn


def test_a_config_without_its_family_module_is_refused_first(tmp_path,
                                                            monkeypatch):
    root, bench_spec = benchroot.build(tmp_path)
    (root / "configs" / "tiny.json").write_text(
        json.dumps(benchroot.tiny_config(model_type="nosuch")))

    def no_device(chips):
        raise AssertionError("the run reached the device")

    monkeypatch.setattr(run, "require_accelerator", no_device)
    with pytest.raises(FileNotFoundError, match=r"reference/nosuch\.py"):
        run.run_cell("tiny.doc", 1, 1.0, False, root=root, spec=bench_spec,
                     t_start=time.perf_counter())
