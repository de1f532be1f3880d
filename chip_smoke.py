#!/usr/bin/env python3
"""Serve qwen3-1.7b at its published widths through the engine on a TPU, and
check what comes out.

    python chip_smoke.py                # one chip: phases 1-5 below
    python chip_smoke.py --four-chips   # four chips: the router fleet and the
                                        # TP=2 engine, each against one chip
    python chip_smoke.py --rehearse     # CPU rehearsal of either mode at the
                                        # SMOKE config, kernels interpreted

Phases, in one process:

1. Device: platform, kind, count, toolchain versions, memory.  No TPU, no
   run (unless ``--rehearse``).
2. Compile cache: ``repro.launch.compile_cache``, before any compile.
3. Kernels: each serving-path kernel at the model's widths through
   ``registry.dispatch`` (compiled, strict tiles) against its ``ref.py``
   oracle; its compiled HLO must hold a ``tpu_custom_call``.
4. Engine: 8 slots, max_len 2048, chunk 256, 16 seeded requests; the three
   launches compiled ahead of serving; every request completes, no fault
   machinery fires, decode compiles once, every launch runs its kernels.
   Then the lockstep-parity check, printed.
5. Agreement: the default (Pallas) policy's logits against the ``*=jnp``
   policy's for the first prefill chunk and 4 decode steps.

Weights are random (seed 0, as ``Server`` makes them); requests come from
``--seed``.  Any failed check raises, so the script exits non-zero; only a
run that passed every phase prints the JSON object that is its last line.
Timings printed on the way are information, labelled with their device.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

SLOTS, MAX_LEN, CHUNK = 8, 2048, 256
N_REQUESTS, PROMPT_LEN, MAX_NEW = 16, (128, 1024), (16, 64)
DECODE_STEPS = 4

# Kernel outputs are bf16, as are the oracle's: each side rounds an f32
# accumulation of the same bf16 products once, to within 2^-9 of the
# element, and the two accumulate in different orders (kernel tiles vs one
# XLA dot).
# 2^-6 of the output's largest magnitude leaves 4x over two roundings.  (The
# f32 scan sums 4096 terms in another order than cumsum: far inside it.)
KERNEL_TOL = 2.0 ** -6
# Logits of two sound paths (the two policies round the residual stream and
# every matmul output to bf16 at different points; one chip against a
# sharded mesh) as a share of the largest logit.  Read on one TPU v5e at
# seed 0: 1.48e-2 to 2.28e-2 over three runs of the Pallas vs jnp check;
# 0.03 leaves 30% over the worst.  Skipping one of the 28 attention blocks
# reads about ten times that (0.28 to 0.37 on a CPU at 28 layers, widths
# 64 to 512), and the control in phase 5 asserts the limit sees it.
LOGIT_TOL = 0.03


def phase(name: str) -> None:
    print(f"\n== {name}", flush=True)


def rel_err(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def device_label(jax) -> str:
    d = jax.devices()[0]
    return f"[{d.platform} {d.device_kind} x{len(jax.devices())}]"


# -- phase 1 ------------------------------------------------------------------

def check_device(jax, args) -> dict:
    from importlib import metadata

    phase("1 device")
    devs = jax.devices()
    d0 = devs[0]
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    print(f"platform={d0.platform} kind={d0.device_kind} count={len(devs)}")
    print(f"jax={jax.__version__} jaxlib={metadata.version('jaxlib')} "
          f"libtpu={libtpu}")
    print(f"memory_stats={d0.memory_stats()}")
    if d0.platform != "tpu" and not args.rehearse:
        raise SystemExit(f"no TPU: JAX found {d0.platform} devices "
                         "(--rehearse runs on the CPU at the SMOKE config)")
    need = 4 if args.four_chips else 1
    if len(devs) < need:
        raise SystemExit(f"need {need} devices, found {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


# -- phase 3 ------------------------------------------------------------------

def kernel_cases(cfg, rng):
    """(name, op, args, kwargs) at the engine's serving shapes."""
    import jax.numpy as jnp
    import numpy as np

    bf16 = jnp.bfloat16
    d, hd, h, kvh = cfg.d_model, cfg.head_dim_, cfg.n_heads, cfg.n_kv_heads

    def normal(*shape, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape) * scale, bf16)

    def mm(name, m, k, n):
        return (f"matmul {name}", "matmul",
                (normal(m, k), normal(k, n, scale=k ** -0.5)), {})

    rows, toks = SLOTS, SLOTS * CHUNK
    qoff = np.sort(rng.integers(0, MAX_LEN - CHUNK, SLOTS)).astype(np.int32)
    pos = rng.integers(0, MAX_LEN - 1, SLOTS).astype(np.int32)
    gqa = {"causal": True, "window": 0, "n_heads": h}

    def attn(sq, sk, kv_dtype=bf16):
        q = normal(rows * h, sq, hd)
        if kv_dtype == jnp.int8:
            k, v = (jnp.asarray(rng.integers(-127, 128, (rows * kvh, sk, hd)),
                                jnp.int8) for _ in range(2))
        else:
            k, v = normal(rows * kvh, sk, hd), normal(rows * kvh, sk, hd)
        return q, k, v

    return [
        # off the serving path, but its body was rewritten for Mosaic (lane
        # roll-and-add in place of cumsum): check it on the chip too
        ("scan", "scan", (jnp.asarray(rng.standard_normal((rows, 4096)),
                                      jnp.float32),), {}),
        mm("qkv prefill chunk", toks, d, cfg.q_dim),
        mm("kv decode", rows, d, cfg.kv_dim),
        mm("out decode", rows, cfg.q_dim, d),
        mm("mlp up prefill chunk", toks, d, cfg.d_ff),
        mm("mlp down decode", rows, cfg.d_ff, d),
        mm("logits decode", rows, d, cfg.vocab_size),
        ("attention prefill chunk", "attention", attn(CHUNK, CHUNK), gqa),
        ("attention prefill continuation", "attention",
         attn(CHUNK, MAX_LEN),
         {**gqa, "q_offset": jnp.asarray(qoff),
          "kv_len": jnp.asarray(qoff + CHUNK)}),
        ("attention per-row decode", "attention", attn(1, MAX_LEN),
         {**gqa, "q_offset": jnp.asarray(pos),
          "kv_len": jnp.asarray(pos + 1)}),
        ("attention int8-KV decode", "attention",
         attn(1, MAX_LEN, jnp.int8),
         {**gqa, "q_offset": jnp.asarray(pos), "kv_len": jnp.asarray(pos + 1),
          "k_scale": jnp.asarray(rng.uniform(0.005, 0.02, rows * kvh),
                                 jnp.float32),
          "v_scale": jnp.asarray(rng.uniform(0.005, 0.02, rows * kvh),
                                 jnp.float32)}),
    ]


def check_kernels(jax, cfg, rng, native: bool) -> None:
    from repro.kernels import ref, registry

    phase("3 kernels (registry.dispatch, compiled, strict tiles)")
    oracles = {"scan": ref.bp_scan_ref, "matmul": ref.matmul_ref,
               "attention": ref.flash_attention_ref}
    for name, op, args, kw in kernel_cases(cfg, rng):
        arrays = {k: v for k, v in kw.items() if hasattr(v, "shape")}
        static = {k: v for k, v in kw.items() if k not in arrays}

        def run(*a, _op=op, _keys=tuple(arrays), _static=static):
            n = len(a) - len(_keys)
            return registry.dispatch(_op, *a[:n], impl="pallas",
                                     interpret=not native,
                                     **dict(zip(_keys, a[n:])), **_static)

        compiled = jax.jit(run).lower(*args, *arrays.values()).compile()
        got = compiled(*args, *arrays.values())
        semantic = {k: v for k, v in kw.items()
                    if k not in registry.get(op).pallas_only}
        with jax.default_matmul_precision("highest"):
            want = oracles[op](*args, **semantic)
        err = rel_err(got, want)
        shapes = " x ".join(str(tuple(a.shape)) for a in args)
        print(f"{name}: {shapes} rel err {err:.3e} (tol {KERNEL_TOL:.3e})")
        assert err <= KERNEL_TOL, f"{name}: {err} > {KERNEL_TOL}"
        if native:
            assert "tpu_custom_call" in compiled.as_text(), \
                f"{name}: no Pallas kernel in the compiled program"


# -- phase 4 ------------------------------------------------------------------

def make_requests(cfg, seed):
    import numpy as np

    from repro.launch.serve import Request

    rng = np.random.default_rng(seed)
    return [Request(uid, rng.integers(3, cfg.vocab_size,
                                      int(rng.integers(*PROMPT_LEN,
                                                       endpoint=True))
                                      ).astype(np.int32),
                    max_new=int(rng.integers(*MAX_NEW, endpoint=True)))
            for uid in range(N_REQUESTS)]


def build_engine(cfg, mesh):
    from repro.launch.engine import Engine
    from repro.models.base import RunOptions
    from repro.runtime import FaultInjector

    return Engine(cfg, mesh, max_batch=SLOTS, max_len=MAX_LEN, chunk=CHUNK,
                  injector=FaultInjector(""), opts=RunOptions())


def serve(jax, engine, reqs, native: bool, label: str) -> dict:
    """Compile the three launches, serve ``reqs``, and check the run."""
    t0 = time.perf_counter()
    compile_s = engine.compile()
    print(f"{label} compile s: " + ", ".join(
        f"{k} {v:.2f}" for k, v in compile_s.items())
          + f" (total {time.perf_counter() - t0:.2f})")
    programs = dict(engine._programs)
    out = engine.run(reqs)
    stats = out["stats"]
    print(f"{label} served {out['tokens']} tokens for {len(reqs)} requests "
          f"in {out['wall_s']:.3f} s wall, {out['tok_per_s']:.1f} tok/s "
          f"(information only); {out['decode_steps']} decode steps, "
          f"{out['prefill_chunks']} prefill launches")
    faults = stats["faults"]
    print(f"faults {faults}")
    for r in reqs:
        assert out["completed"].get(r.uid) == r.max_new == len(r.out), \
            f"request {r.uid}: {len(r.out)} of {r.max_new} tokens"
    for key in ("retries", "faults_injected", "slots_poisoned"):
        assert faults[key] == 0, f"{key} = {faults[key]}"
    # one compile per launch: serving ran the executables compiled above,
    # which refuse any other shape rather than recompile
    assert engine._programs == programs and all(
        engine._programs[k] is p for k, p in programs.items()), \
        "a launch was compiled again while serving"
    print(f"launches compiled once each: {sorted(programs)}")
    if native:
        for name, prog in engine._programs.items():
            assert "tpu_custom_call" in prog.as_text(), \
                f"launch {name} runs no Pallas kernel"
    return out


def report_parity(engine, reqs) -> None:
    """Lockstep parity, printed; where a token differs, how far apart the
    lockstep path's top logits were there."""
    import numpy as np

    from repro.launch.engine import check_lockstep_parity, lockstep_tokens

    ok = check_lockstep_parity(engine, reqs)
    print(f"lockstep parity: {'OK' if ok else 'DIFFERS'}")
    if ok:
        return
    for r in reqs:
        alone, _ = lockstep_tokens(engine, r)
        if alone == r.out:
            continue
        i = next(j for j, (a, b) in enumerate(zip(alone, r.out)) if a != b)
        _, logits = lockstep_tokens(engine, r, logits_at=i)
        runner_up = np.where(np.arange(logits.size) == alone[i], -np.inf,
                             logits).argmax()
        print(f"  uid {r.uid} (prompt {len(r.prompt)}): first differs at "
              f"token {i} of {len(r.out)}: lockstep {alone[i]} "
              f"(logit {logits[alone[i]]:.4f}), engine {r.out[i]} "
              f"(logit {logits[r.out[i]]:.4f}); lockstep runner-up "
              f"{runner_up}, top-2 gap "
              f"{logits[alone[i]] - logits[runner_up]:.4f}")


# -- phase 5 ------------------------------------------------------------------

def path_logits(jax, engine, prompts, impl=None, tokens=None):
    """Logits of the first prefill chunk of each prompt and of
    ``DECODE_STEPS`` decode steps on ``engine``'s model and params, under
    the ambient policy or ``impl``.  Decode inputs are ``tokens`` when
    given (so two paths see identical inputs), else this path's argmax."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.sharding_hints import axis_rules
    from repro.kernels import policy

    model = engine.model
    b = len(prompts)
    toks = np.zeros((b, CHUNK), np.int32)
    lens = np.zeros((b,), np.int32)
    for i, p in enumerate(prompts):
        lens[i] = min(len(p), CHUNK)
        toks[i, :lens[i]] = p[:lens[i]]
    with policy.apply(impl=impl), engine.mesh, \
            axis_rules(engine.rules, engine.mesh):
        chunk = jax.jit(lambda p, t, o, n, c: model.prefill_chunk(
            p, t, o, c, first=True, lens=n))
        step = jax.jit(model.decode_step)
        cache = model.init_cache(b, MAX_LEN)
        logits, cache = chunk(engine.params, jnp.asarray(toks),
                              jnp.zeros((b,), jnp.int32), jnp.asarray(lens),
                              cache)
        out = [np.asarray(logits)]
        pos = lens.copy()
        for s in range(DECODE_STEPS):
            nxt = (tokens[s] if tokens is not None
                   else out[-1].argmax(-1).astype(np.int32))
            logits, cache = step(engine.params, jnp.asarray(nxt)[:, None],
                                 jnp.asarray(pos), cache)
            out.append(np.asarray(logits))
            pos += 1
    return out


def spread_err(got, want) -> float:
    """Largest |got - want| in a row over that row's spread (std) of
    ``want``, worst row: :func:`rel_err` divides by the largest logit,
    which sits several spreads above the rest of its row."""
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.max(np.abs(got - want), -1)
                        / np.maximum(want.std(-1), 1e-30)))


def compare_logits(ref_logits, got_logits, what: str,
                   check: bool = True) -> list:
    """Print (and with ``check``, bound) each step's errors; return them."""
    errs = []
    for s, (a, b) in enumerate(zip(ref_logits, got_logits)):
        err, spread = rel_err(b, a), spread_err(b, a)
        same = float((a.argmax(-1) == b.argmax(-1)).mean())
        label = "prefill chunk" if s == 0 else f"decode step {s}"
        print(f"{what} {label}: rel err {err:.3e} (tol {LOGIT_TOL}), "
              f"spread err {spread:.3e}, top-1 agreement {same:.3f}")
        errs.append((err, spread))
        if check:
            assert err <= LOGIT_TOL, f"{what} {label}: {err} > {LOGIT_TOL}"
    return errs


def teacher_tokens(logits):
    """The decode inputs one path's argmax gives, to feed both paths."""
    return [step.argmax(-1).astype("int32") for step in logits[:DECODE_STEPS]]


def check_agreement(jax, engine, reqs) -> None:
    phase("5 agreement: default (Pallas) policy vs *=jnp")
    prompts = [r.prompt for r in reqs[:SLOTS]]
    pallas = path_logits(jax, engine, prompts)
    xla = path_logits(jax, engine, prompts, impl={"*": "jnp"},
                      tokens=teacher_tokens(pallas))
    compare_logits(xla, pallas, "pallas vs jnp")
    # control: the jnp path with one attention block skipped (its output
    # projection zeroed), on the same inputs
    layers = engine.params["layers"]
    skip = {**engine.params, "layers": {
        **layers, "wo": layers["wo"].at[len(layers["wo"]) // 2].set(0)}}
    engine_params, engine.params = engine.params, skip
    try:
        skipped = path_logits(jax, engine, prompts, impl={"*": "jnp"},
                              tokens=teacher_tokens(pallas))
    finally:
        engine.params = engine_params
    errs = compare_logits(xla, skipped, "control (one attention block "
                          "skipped) vs jnp", check=False)
    assert min(e for e, _ in errs) > LOGIT_TOL, \
        "the logit limit cannot see one attention block skipped"


# -- four chips ---------------------------------------------------------------

def four_chips(jax, cfg, args, native: bool) -> None:
    from repro.launch.mesh import make_debug_mesh, replica_meshes
    from repro.launch.router import Router
    from repro.models.base import RunOptions

    devs = jax.devices()[:4]
    label = device_label(jax)
    phase("4a router: 4 replicas, one chip each, vs one engine")
    meshes = replica_meshes(4, devs)
    ckpt = ROOT / ".smoke_ckpt"
    try:
        router = Router(cfg, meshes, n_replicas=4, ckpt_dir=ckpt,
                        max_batch=SLOTS, max_len=MAX_LEN, chunk=CHUNK,
                        opts=RunOptions())
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)
    placement = [[d.id for d in rep.engine.mesh.devices.flat]
                 for rep in router.replicas]
    print(f"replica devices: {placement}")
    assert sorted(sum(placement, [])) == sorted(d.id for d in devs), placement
    reqs = make_requests(cfg, args.seed)
    for rep in router.replicas:
        rep.engine.compile()
    t0 = time.perf_counter()
    out = router.run(reqs)
    print(f"{label} fleet served {out['tokens']} tokens in "
          f"{time.perf_counter() - t0:.3f} s wall (information only); "
          f"routed {out['counters']['routed']}, deaths "
          f"{out['counters']['replica_deaths']}")
    assert out["counters"]["replica_deaths"] == 0
    single = build_engine(cfg, meshes[0])
    single.params = router.replicas[0].engine.params
    alone = make_requests(cfg, args.seed)
    serve(jax, single, alone, native, f"{label} one-chip engine")
    same = [a.out == r.out for a, r in zip(alone, reqs)]
    print(f"router vs one engine: {sum(same)}/{len(same)} requests "
          "token-identical")
    assert all(same), [r.uid for r, s in zip(reqs, same) if not s]
    del router

    phase("4b engine on the (2, 2) mesh vs the one-chip engine")
    tp = build_engine(cfg, make_debug_mesh(tp=2, devices=devs))
    print(f"mesh {dict(tp.mesh.shape)} over devices "
          f"{[d.id for d in tp.mesh.devices.flat]}")
    tp.params = jax.device_put(single.params,
                               jax.tree.map(lambda a: a.sharding, tp.params))
    prompts = [r.prompt for r in alone[:SLOTS]]
    one = path_logits(jax, single, prompts)
    two = path_logits(jax, tp, prompts, tokens=teacher_tokens(one))
    compare_logits(one, two, "TP=2 vs one chip")
    tp_reqs = make_requests(cfg, args.seed)
    serve(jax, tp, tp_reqs, native, f"{label} TP=2 engine")
    same = sum(a.out == r.out for a, r in zip(alone, tp_reqs))
    print(f"TP=2 engine vs one-chip engine: {same}/{len(alone)} requests "
          "token-identical (information: bf16 near-ties may flip)")


# -- main ---------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the requests and kernel inputs")
    ap.add_argument("--four-chips", action="store_true",
                    help="run the router fleet and the TP=2 engine on four "
                         "chips, each against one chip, and nothing else")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the SMOKE config (interpret mode)")
    args = ap.parse_args(argv)
    if args.rehearse and args.four_chips:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                                   "--xla_force_host_platform_device_count=4")
    # no tile table from outside the checkout: autotune off, its directory
    # inside the checkout
    os.environ["REPRO_AUTOTUNE"] = "off"
    os.environ["REPRO_TUNE_DIR"] = str(ROOT / ".jax_cache" / "autotune")

    import jax
    import numpy as np

    device = check_device(jax, args)
    native = device["platform"] == "tpu"

    from repro.configs import get_config, get_smoke_config
    from repro.kernels import policy
    from repro.launch.compile_cache import setup_compile_cache
    from repro.launch.mesh import make_debug_mesh

    phase("2 compile cache")
    print(f"compile cache: {setup_compile_cache()}")

    # strict tiles: no quiet oracle or jnp.dot fallback anywhere; on the CPU
    # the rehearsal pins every op to its (interpreted) kernel
    pol = policy.ambient().with_(strict_tiles=True, autotune="off")
    if not native:
        pol = pol.with_(impl={"*": "pallas"})
    policy.install(pol)
    cfg = (get_config if native else get_smoke_config)("qwen3-1.7b")
    print(f"config {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads}/{cfg.n_kv_heads} heads of {cfg.head_dim_}, d_ff "
          f"{cfg.d_ff}, vocab {cfg.vocab_size}, {cfg.dtype}; "
          f"{cfg.param_count() / 1e9:.3f} B params")

    if args.four_chips:
        four_chips(jax, cfg, args, native)
    else:
        check_kernels(jax, cfg, np.random.default_rng(args.seed), native)
        phase("4 engine")
        engine = build_engine(
            cfg, make_debug_mesh(tp=min(2, len(jax.devices()))))
        reqs = make_requests(cfg, args.seed)
        serve(jax, engine, reqs, native, device_label(jax))
        report_parity(engine, reqs)
        check_agreement(jax, engine, reqs)

    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
