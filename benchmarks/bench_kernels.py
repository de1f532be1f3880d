"""Kernel microbenchmarks, resolved through the kernel registry.

For every registered op this times:

  * ``ref``            — the jnp oracle path (``dispatch(..., impl="ref")``,
                         the per-call policy override)
                         — the XLA numbers that matter on this CPU container;
  * ``pallas_fixed``   — the Pallas path (interpret mode on CPU) with the
                         pre-substrate hard-coded tiles (128 / 512 / 256);
  * ``pallas_planned`` — the Pallas path with planner-derived tiles;
  * ``pallas_tuned``   — the planned path overlaid by the persisted autotune
                         table (``benchmarks/autotune.py`` populates it;
                         falls back to the analytic plan on a cold cache).

The ``matmul_strassen`` case additionally records ``pallas_classical_us``
(planner tiles, backend forced classical) next to ``pallas_planned_us``
(which routes the planner's Strassen choice at that shape), so the
crossover claim — Strassen beats classical above the modeled edge — is
measured, not asserted.  The ``mlp`` case times the model-level
``gated_mlp`` under a jnp vs a pallas execution-policy scope (the registry
route model traffic takes).

Interpret-mode wall times are NOT meaningful device performance; they are
recorded so the before/after planner tiling delta is machine-checkable.  On
the TPU target the same dispatch compiles natively.  Emits
``name,us_per_call,derived`` CSV rows and (via ``main(json_path=...)``) a
machine-readable ``BENCH_kernels.json``.

``--ops`` filters cases by name or registry op (e.g. ``--ops matmul`` runs
the matmul + matmul_strassen arms only — the CI smoke arm); a filtered run
skips the JSON write unless ``--json`` is given explicitly, and when it
does write it merges its arms into the existing file's ``ops`` instead of
clobbering the others.  The ``serve_faulted`` arm measures the engine's
fault-recovery overhead (clean vs seeded-fault-plan run, tokens asserted
identical).
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.kernels import autotune, planner, registry  # noqa: E402

# the hard-coded tile constants the substrate replaced, kept here as the
# benchmark's "before" arm
LEGACY_TILES = {
    "scan": {"block": 512},
    "matmul": {"bm": 128, "bn": 128, "bk": 128},
    # pre-substrate "before": fixed tiles AND no Strassen schedule
    "matmul_strassen": {"bm": 128, "bn": 128, "bk": 128,
                        "backend": "classical"},
    "transpose": {"bt": 128},
    "attention": {"q_block": 256, "kv_block": 256},
    "attention_decode": {"q_block": 256, "kv_block": 256},
    "fft": {"n1": 1},  # pre-substrate: no four-step split (one dense DFT)
}


def timeit(fn, *args, iters=5):
    """The autotune harness's discipline (median-of-k per-call, compile
    excluded), shared so the arms and the search winners are comparable."""
    return autotune.measure_us(fn, args, iters=iters)


def _cases():
    """Arm name -> case.  ``op`` is the registry op the arm dispatches (the
    decode arm reuses ``attention`` with a query offset over a KV cache)."""
    key = jax.random.key
    x = jax.random.normal(key(0), (8, 8192), jnp.float32)
    a = jax.random.normal(key(1), (512, 512), jnp.float32)
    b = jax.random.normal(key(2), (512, 512), jnp.float32)
    q = jax.random.normal(key(3), (8, 512, 64), jnp.float32)
    k = jax.random.normal(key(4), (8, 512, 64), jnp.float32)
    v = jax.random.normal(key(5), (8, 512, 64), jnp.float32)
    # decode regime: one query row per head over a mostly-full 1024-slot
    # cache (static kv_len -> the kernel's planner-aware grid shrink)
    qd = jax.random.normal(key(8), (8, 1, 64), jnp.float32)
    kc = jax.random.normal(key(9), (8, 1024, 64), jnp.float32)
    vc = jax.random.normal(key(10), (8, 1024, 64), jnp.float32)
    kv_len = 1000
    xc = (jax.random.normal(key(6), (4, 1024))
          + 1j * jax.random.normal(key(7), (4, 1024))).astype(jnp.complex64)
    # the largest benched square shape, above the modeled crossover, with
    # the Strassen schedule requested (the planner never picks it unasked);
    # the classical extra arm measures the same shape without it
    a2 = jax.random.normal(key(11), (1024, 1024), jnp.float32)
    b2 = jax.random.normal(key(12), (1024, 1024), jnp.float32)
    return {
        "scan": dict(op="scan", args=(x,), kwargs={}, label="8x8192",
                     derived=lambda us: f"{x.size * 4 * 2 / (us / 1e6) / 1e9:.2f}GB/s"),
        "matmul": dict(op="matmul", args=(a, b), kwargs={}, label="512",
                       derived=lambda us: f"{2 * 512**3 / (us / 1e6) / 1e9:.1f}GFLOP/s"),
        "matmul_strassen": dict(
            op="matmul", args=(a2, b2), kwargs={"backend": "strassen"},
            label="1024",
            extra_arms={"pallas_classical": {"backend": "classical"}},
            derived=lambda us: f"{2 * 1024**3 / (us / 1e6) / 1e9:.1f}GFLOP/s"),
        "transpose": dict(op="transpose", args=(a,), kwargs={}, label="512",
                          derived=lambda us: f"{a.size * 4 * 2 / (us / 1e6) / 1e9:.2f}GB/s"),
        "attention": dict(op="attention", args=(q, k, v),
                          kwargs={"causal": False, "window": 0},
                          label="8x512x64",
                          derived=lambda us: f"{4 * 8 * 512 * 512 * 64 / (us / 1e6) / 1e9:.1f}GFLOP/s"),
        "attention_decode": dict(op="attention", args=(qd, kc, vc),
                                 kwargs={"causal": True, "window": 0,
                                         "q_offset": kv_len - 1,
                                         "kv_len": kv_len},
                                 label="8x1q_1024kv",
                                 derived=lambda us: f"{4 * 8 * kv_len * 64 / (us / 1e6) / 1e9:.2f}GFLOP/s"),
        "fft": dict(op="fft", args=(xc,), kwargs={}, label="4x1024",
                    derived=lambda us: f"{5 * 4 * 1024 * 10 / (us / 1e6) / 1e9:.2f}GFLOP/s"),
    }


def _bench_mlp() -> dict:
    """Model-level arm: ``gated_mlp`` under a jnp vs a pallas execution
    policy scope — what serve/train traffic sees once model matmuls dispatch
    through the substrate."""
    from repro.kernels import policy
    from repro.models import common as model_common

    key = jax.random.key
    x = jax.random.normal(key(20), (512, 256), jnp.float32)
    wg = jax.random.normal(key(21), (256, 1024), jnp.float32) * 0.05
    wu = jax.random.normal(key(22), (256, 1024), jnp.float32) * 0.05
    wd = jax.random.normal(key(23), (1024, 256), jnp.float32) * 0.05
    flops = 3 * 2 * 512 * 256 * 1024
    entry: dict = {"op": "mlp", "shape": "512x256x1024"}
    with autotune.mode_scope("off"):
        for arm, backend in (("jnp", "jnp"), ("pallas_planned", "pallas")):
            with policy.apply(impl={"matmul": backend}):
                fn = jax.jit(lambda *a: model_common.gated_mlp(*a))
                us = timeit(fn, x, wg, wu, wd)
            entry[f"{arm}_us"] = round(us, 1)
            print(f"kernel_mlp_{arm}_512x256x1024,{us:.0f},"
                  f"{flops / (us / 1e6) / 1e9:.1f}GFLOP/s")
    return entry


def _bench_serve_decode() -> dict:
    """Serving arm: one GQA decode attention step (b=4, h=8, kvh=1 — the
    deepest grouping — over a 4096-slot cache), timed as the model layer runs
    it.  ``native`` is the kernel-native GQA route (K/V at their native head
    count, the kv index map sharing blocks across the group); ``prerepeat``
    reconstructs the pre-PR adapter (materialize ``repeat_kv`` to the full
    query head count, then dispatch) — the cache-sized copy the fast path
    deletes, re-paid every decode step.  Reported as tokens/sec (b tokens
    per step through this one attention layer) so the serving claim is
    machine-checkable; interpret-mode absolute numbers are still not device
    performance."""
    from repro.kernels import policy
    from repro.models import common as model_common

    key = jax.random.key
    b, h, kvh, hd, sk = 4, 8, 1, 64, 4096
    q = jax.random.normal(key(30), (b, 1, h, hd), jnp.float32)
    k = jax.random.normal(key(31), (b, sk, kvh, hd), jnp.float32)
    v = jax.random.normal(key(32), (b, sk, kvh, hd), jnp.float32)
    q_pos = jnp.full((1,), sk - 1, jnp.int32)
    k_pos = jnp.arange(sk, dtype=jnp.int32)
    entry: dict = {"op": "attention", "shape": f"{b}x1q_{sk}kv_gqa{h // kvh}"}

    def native(q, k, v):
        return model_common.attention(q, k, v, q_pos, k_pos, causal=True)

    def prerepeat(q, k, v):
        # the old adapter: repeat the cache to h heads, then fold + dispatch
        kr = model_common.repeat_kv(k, h // kvh)
        vr = model_common.repeat_kv(v, h // kvh)

        def fold(x):
            return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], hd)

        out = registry.dispatch(
            "attention", fold(q), fold(kr), fold(vr), causal=True,
            q_offset=sk - 1, kv_len=sk, impl="pallas")
        return out.reshape(b, h, 1, hd).transpose(0, 2, 1, 3)

    with autotune.mode_scope("off"):
        for arm, fn in (("native", native), ("prerepeat", prerepeat)):
            with policy.apply(impl={"attention": "pallas"}):
                us = timeit(jax.jit(fn), q, k, v)
            entry[f"{arm}_us"] = round(us, 1)
            entry[f"{arm}_tok_per_s"] = round(b / (us / 1e6), 1)
            print(f"kernel_serve_decode_{arm}_{entry['shape']},{us:.0f},"
                  f"{b / (us / 1e6):.1f}tok/s")
    return entry


def _bench_serve_continuous() -> dict:
    """Serving-loop arm: the continuous-batching engine vs the lockstep
    wave baseline, end to end (prefill + greedy decode) on a skewed
    workload — one straggler (``max_new=24``) rides with three short
    requests (``max_new=2``) per wave of 4 slots, three waves.  Lockstep
    pays the straggler's steps for every row of its wave; the engine evicts
    the short rows and backfills from the queue, so the same model serves
    the same tokens in far fewer batched decode launches.  Both systems run
    the same jitted model functions on the same params (seed 0) and are
    warmed up (compile excluded) before timing; prompts fit one prefill
    chunk.  Reported as tokens/sec; interpret-mode absolute numbers are
    still not device performance — the launch-count ratio is the claim."""
    import numpy as np

    from repro.configs import get_smoke_config
    from repro.launch.engine import Engine
    from repro.launch.mesh import make_debug_mesh
    from repro.launch.serve import Request, Server
    from repro.models.base import RunOptions

    cfg = get_smoke_config("qwen3-1.7b")
    mesh = make_debug_mesh(tp=min(2, len(jax.devices())))
    slots, waves = 4, 3
    rng = np.random.default_rng(0)
    spec = []  # (prompt, max_new): one straggler per lockstep wave.  Equal
    # prompt lengths so the lockstep wave needs no left-padding — batched
    # lockstep, run-alone, and the engine then all emit identical tokens
    for _ in range(waves):
        for mn in (24, 2, 2, 2):
            spec.append((rng.integers(3, cfg.vocab_size, 12).astype(np.int32),
                         mn))

    def requests():
        return [Request(i, p, max_new=mn) for i, (p, mn) in enumerate(spec)]

    server = Server(cfg, mesh, max_batch=slots, max_len=64)
    engine = Engine(cfg, mesh, max_batch=slots, max_len=64, chunk=16,
                    opts=RunOptions())
    # warmup: compile both systems' jitted paths outside the timed region
    server.run_batch([Request(0, spec[0][0], max_new=2)])
    engine.run([Request(0, spec[0][0], max_new=2)])

    reqs = requests()
    lock_s = 0.0
    for w in range(waves):  # lockstep serves in waves of the slot count
        lock_s += server.run_batch(reqs[w * slots:(w + 1) * slots])["wall_s"]
    lock_toks = sum(len(r.out) for r in reqs)

    creqs = requests()
    cont = engine.run(creqs)
    assert [r.out for r in creqs] == [r.out for r in reqs], \
        "continuous tokens diverge from lockstep tokens"

    entry = {
        "op": "serve", "shape": f"{slots}slots_{len(spec)}reqs_skewed",
        "lockstep_tok_per_s": round(lock_toks / max(lock_s, 1e-9), 1),
        "continuous_tok_per_s": round(cont["tok_per_s"], 1),
        "speedup": round((cont["tok_per_s"] * max(lock_s, 1e-9)) / lock_toks, 2),
        "continuous_decode_steps": cont["decode_steps"],
        "continuous_prefill_chunks": cont["prefill_chunks"],
        "telemetry": cont["telemetry"],
    }
    print(f"kernel_serve_lockstep_{entry['shape']},"
          f"{lock_s / max(lock_toks, 1) * 1e6:.0f},"
          f"{entry['lockstep_tok_per_s']}tok/s")
    print(f"kernel_serve_continuous_{entry['shape']},"
          f"{cont['wall_s'] / max(cont['tokens'], 1) * 1e6:.0f},"
          f"{entry['continuous_tok_per_s']}tok/s "
          f"({entry['speedup']}x lockstep)")
    return entry


def _bench_serve_continuous_hybrid() -> dict:
    """The non-dense serving claim: the same continuous-batching engine
    loop serves the HYBRID family (LRU/conv recurrent state in a
    ``StateCarry`` layout, parked rows riding identity updates) on the same
    skewed straggler workload as the dense arm.  ``chunk`` covers the
    prompts so prefill is single-chunk (the LRU h0-fold reassociates across
    chunk boundaries); tokens are asserted identical to lockstep."""
    import numpy as np

    from repro.configs import get_smoke_config
    from repro.launch.engine import Engine
    from repro.launch.mesh import make_debug_mesh
    from repro.launch.serve import Request, Server
    from repro.models.base import RunOptions

    cfg = get_smoke_config("recurrentgemma-2b")
    mesh = make_debug_mesh(tp=min(2, len(jax.devices())))
    slots, waves = 4, 2
    rng = np.random.default_rng(0)
    spec = []
    for _ in range(waves):
        for mn in (24, 2, 2, 2):
            spec.append((rng.integers(3, cfg.vocab_size, 12).astype(np.int32),
                         mn))

    def requests():
        return [Request(i, p, max_new=mn) for i, (p, mn) in enumerate(spec)]

    server = Server(cfg, mesh, max_batch=slots, max_len=64)
    engine = Engine(cfg, mesh, max_batch=slots, max_len=64, chunk=16,
                    opts=RunOptions())
    server.run_batch([Request(0, spec[0][0], max_new=2)])
    engine.run([Request(0, spec[0][0], max_new=2)])

    reqs = requests()
    lock_s = 0.0
    for w in range(waves):
        lock_s += server.run_batch(reqs[w * slots:(w + 1) * slots])["wall_s"]
    lock_toks = sum(len(r.out) for r in reqs)

    creqs = requests()
    cont = engine.run(creqs)
    assert [r.out for r in creqs] == [r.out for r in reqs], \
        "hybrid continuous tokens diverge from lockstep tokens"

    entry = {
        "op": "serve", "shape": f"hybrid_{slots}slots_{len(spec)}reqs_skewed",
        "lockstep_tok_per_s": round(lock_toks / max(lock_s, 1e-9), 1),
        "continuous_tok_per_s": round(cont["tok_per_s"], 1),
        "speedup": round((cont["tok_per_s"] * max(lock_s, 1e-9)) / lock_toks, 2),
        "continuous_decode_steps": cont["decode_steps"],
        "continuous_prefill_chunks": cont["prefill_chunks"],
        "telemetry": cont["telemetry"],
    }
    print(f"kernel_serve_lockstep_{entry['shape']},"
          f"{lock_s / max(lock_toks, 1) * 1e6:.0f},"
          f"{entry['lockstep_tok_per_s']}tok/s")
    print(f"kernel_serve_continuous_{entry['shape']},"
          f"{cont['wall_s'] / max(cont['tokens'], 1) * 1e6:.0f},"
          f"{entry['continuous_tok_per_s']}tok/s "
          f"({entry['speedup']}x lockstep)")
    return entry


def _bench_serve_faulted() -> dict:
    """Recovery-overhead arm: the SAME workload through the engine clean,
    then under a seeded fault plan firing all three fault kinds — a decode
    raise (bounded retry), a prefill straggler delay, and a poisoned slot
    (non-finite row -> bisect, evict, resume from its last snapshot).  The
    fresh injector is installed AFTER warmup so the warmup run cannot burn
    the plan's entries.  Tokens are asserted request-for-request identical
    and ``snapshot_restores >= 1`` — the measured claim is that recovery
    costs bounded wall time, never correctness."""
    import numpy as np

    from repro.configs import get_smoke_config
    from repro.launch.engine import Engine
    from repro.launch.mesh import make_debug_mesh
    from repro.launch.serve import Request
    from repro.models.base import RunOptions
    from repro.runtime.fault_tolerance import FaultInjector

    plan = "decode@1=raise,prefill@1=delay:0.05,slot@1=nan_logits:3"
    cfg = get_smoke_config("qwen3-1.7b")
    mesh = make_debug_mesh(tp=min(2, len(jax.devices())))
    rng = np.random.default_rng(0)
    spec = [(rng.integers(3, cfg.vocab_size, 12).astype(np.int32), mn)
            for mn in (8, 6, 8, 4, 6, 8)]

    def requests():
        return [Request(i, p, max_new=mn) for i, (p, mn) in enumerate(spec)]

    engine = Engine(cfg, mesh, max_batch=3, max_len=64, chunk=8,
                    snapshot_every=2, injector=FaultInjector(""),
                    opts=RunOptions())
    # warmup = the full workload once, so compiles AND the snapshot path's
    # one-time eager lowering land outside both timed runs
    engine.run(requests())

    clean_reqs = requests()
    clean = engine.run(clean_reqs)

    engine.injector = FaultInjector(plan)
    faulted_reqs = requests()
    faulted = engine.run(faulted_reqs)

    tel = faulted["telemetry"]
    assert [r.out for r in faulted_reqs] == [r.out for r in clean_reqs], \
        "faulted tokens diverge from the clean run"
    assert tel["retries"] >= 1, "the decode raise never retried"
    assert tel["slots_poisoned"] == 1, "the poisoned slot was not bisected"
    assert tel["snapshot_restores"] >= 1, "recovery skipped the snapshot"

    entry = {
        "op": "serve", "shape": "faulted_3slots_6reqs", "plan": plan,
        "clean_tok_per_s": round(clean["tok_per_s"], 1),
        "faulted_tok_per_s": round(faulted["tok_per_s"], 1),
        "recovery_overhead": round(
            faulted["wall_s"] / max(clean["wall_s"], 1e-9), 2),
        "faulted_decode_steps": faulted["decode_steps"],
        "clean_decode_steps": clean["decode_steps"],
        "telemetry": tel,
    }
    print(f"kernel_serve_clean_{entry['shape']},"
          f"{clean['wall_s'] / max(clean['tokens'], 1) * 1e6:.0f},"
          f"{entry['clean_tok_per_s']}tok/s")
    print(f"kernel_serve_faulted_{entry['shape']},"
          f"{faulted['wall_s'] / max(faulted['tokens'], 1) * 1e6:.0f},"
          f"{entry['faulted_tok_per_s']}tok/s "
          f"({entry['recovery_overhead']}x clean, tokens identical)")
    return entry


def main(json_path: str | None = None, ops: list[str] | None = None) -> dict:
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    results: dict[str, dict] = {}
    cases = _cases()
    if ops:
        cases = {n: c for n, c in cases.items() if n in ops or c["op"] in ops}
    for name, case in cases.items():
        op, args, kwargs = case["op"], case["args"], case["kwargs"]
        plan = dict(registry.get(op).plan(*args))
        entry: dict = {"op": op, "shape": case["label"], "planned_tiles": plan}

        # the oracle takes the semantic kwargs only (no schedule request)
        semantic = {k: v for k, v in kwargs.items()
                    if k not in registry.get(op).pallas_only}
        ref_fn = jax.jit(lambda *a, _n=op, _kw=semantic: registry.dispatch(
            _n, *a, impl="ref", **_kw))
        us = timeit(ref_fn, *args)
        entry["ref_us"] = round(us, 1)
        print(f"kernel_{name}_ref_{case['label']},{us:.0f},{case['derived'](us)}")

        # fixed/planned arms pin the mode off: an inherited REPRO_AUTOTUNE +
        # warm table must not overlay tuned tiles onto the comparison baseline
        arms = [("pallas_fixed", LEGACY_TILES[name]), ("pallas_planned", {})]
        arms += list(case.get("extra_arms", {}).items())
        with autotune.mode_scope("off"):
            for arm, tiles in arms:
                fn = jax.jit(lambda *a, _n=op, _kw={**kwargs, **tiles}:
                             registry.dispatch(_n, *a, impl="pallas", **_kw))
                us = timeit(fn, *args, iters=5)
                entry[f"{arm}_us"] = round(us, 1)
                print(f"kernel_{name}_{arm}_{case['label']},{us:.0f},interpret")

        # tuned arm: same dispatch, persisted measurements replayed on top of
        # the plan (identical to pallas_planned when the table has no entry);
        # the lookup keys the semantic kwargs (masking regime / decode flag)
        # and mirrors replay's cross-shape interpolation fallback, so the
        # recorded tiles are the ones the timed dispatch actually ran
        tuned = autotune.lookup(op, *args, kwargs=kwargs)
        if tuned is None:
            tuned = autotune.nearest_plan(op, *args, kwargs=kwargs)
        entry["tuned_tiles"] = autotune.snap_plan(op, args, tuned) if tuned else plan
        with autotune.mode_scope("replay"):
            fn = jax.jit(lambda *a, _n=op, _kw=kwargs: registry.dispatch(
                _n, *a, impl="pallas", **_kw))
            us = timeit(fn, *args, iters=5)
        entry["pallas_tuned_us"] = round(us, 1)
        print(f"kernel_{name}_pallas_tuned_{case['label']},{us:.0f},interpret")
        results[name] = entry

    if ops is None or "mlp" in ops:
        results["mlp"] = _bench_mlp()
    if ops is None or "serve_decode" in ops:
        results["serve_decode"] = _bench_serve_decode()
    if ops is None or "serve_continuous" in ops:
        results["serve_continuous"] = _bench_serve_continuous()
    if ops is None or "serve_continuous_hybrid" in ops:
        results["serve_continuous_hybrid"] = _bench_serve_continuous_hybrid()
    if ops is None or "serve_faulted" in ops:
        results["serve_faulted"] = _bench_serve_faulted()

    from repro.kernels import policy
    dp = planner.device_params()
    prov = autotune.provenance()
    payload = {
        "device": {"platform": dp.platform, "kind": dp.kind,
                   "fast_bytes": dp.fast_bytes, "line_bytes": dp.line_bytes},
        # provenance: the ambient execution policy and autotune table the
        # numbers were measured under
        "policy": policy.current().describe(),
        "autotune": prov,
        "ops": results,
    }
    if json_path:
        out = Path(json_path)
        if ops and out.exists():
            # a filtered run UPDATES its arms in the existing file instead
            # of clobbering the others (device/policy provenance refreshes)
            prior = json.loads(out.read_text()).get("ops", {})
            payload["ops"] = {**prior, **results}
        out.write_text(json.dumps(payload, indent=2) + "\n")
        print(f"# wrote {json_path}")
    return payload


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--ops", default="",
                    help="comma-separated case/op filter (e.g. 'matmul' runs "
                         "the matmul + matmul_strassen smoke arms)")
    ap.add_argument("--json", default=None,
                    help="output path (default: BENCH_kernels.json for a "
                         "full run; filtered runs print only)")
    cli = ap.parse_args()
    wanted = [o for o in cli.ops.split(",") if o] or None
    path = cli.json or (None if wanted else str(REPO / "BENCH_kernels.json"))
    main(json_path=path, ops=wanted)
