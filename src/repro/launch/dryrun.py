"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell with
ShapeDtypeStruct inputs (no allocation), record memory/cost/collective
analysis.  The environment lines below MUST run before jax is imported: jax
locks the platform and the device count on first init.  They pin this
process, and the ``--all`` children that inherit its environment, to the
CPU's 512 virtual devices — a dry-run never takes a chip — and add to
``XLA_FLAGS`` rather than replace it.

Usage:
  python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all            # every cell, subprocess each
  python -m repro.launch.dryrun --all --mesh multi
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=512").strip()

import argparse  # noqa: E402
import gzip
import json
import subprocess
import sys
import time
import traceback
from pathlib import Path

REPO = Path(__file__).resolve().parents[3]
OUT_DIR = REPO / "experiments" / "dryrun"
HLO_DIR = OUT_DIR / "hlo"

# v5e-like hardware model (per chip)
PEAK_FLOPS = 197e12  # bf16
HBM_BW = 819e9  # bytes/s
ICI_BW = 50e9  # bytes/s/link


def cell_id(arch: str, shape: str, mesh: str, tag: str = "") -> str:
    t = f"_{tag}" if tag else ""
    return f"{arch}_{shape}_{mesh}{t}"


# hand-tuned microbatch counts for the heavy cells (planner table — measured
# to fit 16 GB/device HBM; see EXPERIMENTS.md §Dry-run)
_MICROBATCH_TABLE = {
    ("llama-3.2-vision-90b", "train_4k", "single"): 16,
    ("llama-3.2-vision-90b", "train_4k", "multi"): 8,  # = global_batch/dp
    ("qwen3-32b", "train_4k", "single"): 2,
    ("qwen3-32b", "train_4k", "multi"): 2,
}


def planner_defaults(cfg, shape, mesh) -> dict:
    """Resource-aware RunOptions chosen by the planner (models never see the
    mesh; the scheduler does — the paper's division of labor).

    * microbatches: tuned table for the heavy cells; fallback formula keeps
      the per-device residual activation stack under ~2 GB.
    * moe_groups: one dispatch group per data shard.
    """
    dp = 1
    for a in ("pod", "data"):
        if a in mesh.shape:
            dp *= mesh.shape[a]
    tp = mesh.shape.get("model", 1)
    mesh_kind = "multi" if "pod" in mesh.shape else "single"
    out: dict = {}
    if cfg.n_experts:
        out["moe_groups"] = dp
    if shape.kind == "train":
        key = (cfg.name, shape.name, mesh_kind)
        if key in _MICROBATCH_TABLE:
            out["microbatches"] = _MICROBATCH_TABLE[key]
            return out
        layers = cfg.n_layers + (cfg.encoder_layers or 0)
        per_dev_tokens = max(shape.global_batch // dp, 1) * shape.seq_len / tp
        est = layers * per_dev_tokens * cfg.d_model * 2  # bf16 residual stack
        micro = 1
        while est / micro > 6e9 and micro < max(shape.global_batch // dp, 1):
            micro *= 2
        if micro > 1:
            out["microbatches"] = micro
    return out


def run_cell(arch: str, shape_name: str, mesh_kind: str, *, opts_kw: dict | None = None,
             save_hlo: bool = True, tag: str = "") -> dict:
    import jax

    from repro.configs import SHAPES, get_config
    from repro.core import planner
    from repro.core.sharding_hints import axis_rules, default_rules
    from repro.launch import hlo_analysis
    from repro.launch.mesh import make_production_mesh, mesh_device_count
    from repro.launch.steps import build_step_bundle
    from repro.models.base import RunOptions

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    rec: dict = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind, "tag": tag,
        "kind": shape.kind, "seq_len": shape.seq_len, "global_batch": shape.global_batch,
        "status": "ok",
    }

    if shape_name == "long_500k" and not cfg.supports_long_context:
        rec["status"] = "SKIP(full-attention)"
        return rec

    opts_kw = dict(opts_kw or {})
    rules_override = opts_kw.pop("axis_rules", {})
    param_mode = opts_kw.pop("param_sharding", "fsdp")
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    opts_kw = {**planner_defaults(cfg, shape, mesh), **opts_kw}
    opts = RunOptions(**opts_kw)
    rec["opts"] = {**opts_kw, "param_sharding": param_mode}
    n_dev = mesh_device_count(mesh)
    rec["n_devices"] = n_dev

    bundle = build_step_bundle(cfg, shape, opts)

    in_shardings = []
    for arg, kind in zip(bundle.args, bundle.kinds):
        if kind == "params":
            in_shardings.append(planner.named(
                planner.plan_params(arg, mesh, mode=param_mode), mesh))
        elif kind == "opt":
            spec = {
                "step": jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
                "master": planner.named(planner.plan_params(arg["master"], mesh), mesh),
                "m": planner.named(planner.plan_params(arg["m"], mesh), mesh),
                "v": planner.named(planner.plan_params(arg["v"], mesh), mesh),
            }
            in_shardings.append(spec)
        elif kind == "batch":
            in_shardings.append(planner.named(planner.plan_batch(arg, mesh), mesh))
        elif kind == "cache":
            in_shardings.append(planner.named(planner.plan_cache(arg, mesh), mesh))
        else:  # scalar
            in_shardings.append(jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()))

    # outputs: params/opt/cache keep their input layout (donated); rest auto
    if bundle.name == "train_step":
        out_shardings = (in_shardings[0], in_shardings[1], None)
        donate = (0, 1)
    elif bundle.name == "prefill_step":
        cache_spec = planner.named(planner.plan_cache(
            jax.eval_shape(bundle.fn, *bundle.args)[1], mesh), mesh)
        out_shardings = (None, cache_spec)
        donate = ()
    else:  # serve_step
        out_shardings = (None, in_shardings[3])
        donate = (3,)

    rules = default_rules(mesh)
    rules.update(rules_override)
    t0 = time.time()
    with mesh, axis_rules(rules, mesh):
        jitted = jax.jit(bundle.fn, in_shardings=tuple(in_shardings),
                         out_shardings=out_shardings, donate_argnums=donate)
        lowered = jitted.lower(*bundle.args)
        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)

    ma = compiled.memory_analysis()
    if ma is not None:
        rec["memory"] = {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_bytes_est": ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes,
        }
    ca = compiled.cost_analysis() or {}
    rec["xla_cost"] = {"flops": ca.get("flops"), "bytes_accessed": ca.get("bytes accessed")}

    txt = compiled.as_text()
    stats = hlo_analysis.analyze(txt, n_devices_default=n_dev)
    rec["hlo"] = stats.as_dict()

    # roofline terms (per device quantities vs per-chip peaks)
    rec["roofline"] = {
        "compute_s": stats.flops / PEAK_FLOPS,
        "memory_s": stats.hbm_bytes / HBM_BW,
        "collective_s": stats.collective_bytes / ICI_BW,
    }
    dom = max(rec["roofline"], key=rec["roofline"].get)
    rec["roofline"]["dominant"] = dom

    if save_hlo:
        HLO_DIR.mkdir(parents=True, exist_ok=True)
        with gzip.open(HLO_DIR / f"{cell_id(arch, shape_name, mesh_kind, tag)}.hlo.gz",
                       "wt") as f:
            f.write(txt)
    return rec


def all_cells(mesh_kinds: list[str]) -> list[tuple[str, str, str]]:
    from repro.configs import SHAPES, list_archs

    cells = []
    for mesh_kind in mesh_kinds:
        for arch in list_archs():
            for shape in SHAPES:
                cells.append((arch, shape, mesh_kind))
    return cells


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--tag", default="", help="variant tag for perf experiments")
    ap.add_argument("--opts", default="", help="JSON RunOptions overrides")
    ap.add_argument("--impl", default="",
                    help="execution-policy impl map, op=backend[,op=backend] "
                         "('*' wildcard) — exported as REPRO_IMPL so every "
                         "lowered cell (including --all subprocesses) "
                         "assembles the same ambient policy")
    ap.add_argument("--timeout", type=int, default=1800)
    ap.add_argument("--no-hlo", action="store_true")
    args = ap.parse_args()
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()

    if args.impl:
        from repro.kernels import policy

        policy.parse_impl_spec(args.impl)  # validate (impl + knobs) pre-fan-out
        os.environ["REPRO_IMPL"] = args.impl

    OUT_DIR.mkdir(parents=True, exist_ok=True)

    if args.all:
        mesh_kinds = ["single", "multi"] if args.mesh == "both" else [args.mesh]
        cells = all_cells(mesh_kinds)
        failures = 0
        for arch, shape, mesh_kind in cells:
            out_file = OUT_DIR / f"{cell_id(arch, shape, mesh_kind, args.tag)}.json"
            if out_file.exists():
                print(f"[skip-cached] {out_file.name}")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
                   "--shape", shape, "--mesh", mesh_kind]
            if args.tag:
                cmd += ["--tag", args.tag]
            if args.opts:
                cmd += ["--opts", args.opts]
            if args.no_hlo:
                cmd += ["--no-hlo"]
            t0 = time.time()
            try:
                r = subprocess.run(cmd, capture_output=True, text=True,
                                   timeout=args.timeout,
                                   env={**os.environ, "PYTHONPATH": str(REPO / "src")})
                ok = r.returncode == 0
            except subprocess.TimeoutExpired:
                ok = False
                r = None
            if not ok:
                failures += 1
                err = (r.stderr[-2000:] if r else "TIMEOUT")
                rec = {"arch": arch, "shape": shape, "mesh": mesh_kind,
                       "tag": args.tag, "status": f"FAIL: {err}"}
                out_file.write_text(json.dumps(rec, indent=1))
                print(f"[FAIL {time.time()-t0:6.0f}s] {arch} {shape} {mesh_kind}")
            else:
                print(f"[ok   {time.time()-t0:6.0f}s] {arch} {shape} {mesh_kind}")
        print(f"done, {failures} failures / {len(cells)} cells")
        return 1 if failures else 0

    opts_kw = json.loads(args.opts) if args.opts else {}
    rec = run_cell(args.arch, args.shape, args.mesh, opts_kw=opts_kw,
                   save_hlo=not args.no_hlo, tag=args.tag)
    out_file = OUT_DIR / f"{cell_id(args.arch, args.shape, args.mesh, args.tag)}.json"
    out_file.write_text(json.dumps(rec, indent=1))
    print(json.dumps(rec, indent=1))
    return 0 if rec["status"].startswith(("ok", "SKIP")) else 1


if __name__ == "__main__":
    sys.exit(main())
