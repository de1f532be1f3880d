"""End-to-end training driver.

Wires together: config -> model -> PWS planner shardings -> data pipeline ->
fault-tolerant loop with async checkpointing.  Runs on any mesh (tests use a
small host-device mesh; the production meshes come from mesh.py).

Kernel backends resolve through the ambient ``repro.kernels.policy``
execution policy; ``--impl op=backend[,op=backend]`` installs a process
policy (op: a registered kernel name or ``*``; backend: ``auto`` | ``jnp``
| ``pallas``; a bare backend means ``*=backend``) — it replaces the old
``--attention-impl``/``--matmul-impl`` pair.  ``REPRO_IMPL`` (same grammar)
works without a flag.

CLI (CPU-scale example):
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --steps 50 \
      --reduced --batch 8 --seq 256 --impl '*=pallas'
"""
from __future__ import annotations

import argparse
import logging
import time
from pathlib import Path

import jax
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import SHAPES, get_config, get_smoke_config
from repro.core import planner
from repro.core.sharding_hints import axis_rules, default_rules
from repro.data import DataConfig, SyntheticLMDataset
from repro.launch.steps import make_train_step
from repro.models import build_model
from repro.models.base import RunOptions
from repro.optim import AdamWConfig, adamw_init
from repro.runtime import FaultTolerantRunner

log = logging.getLogger("repro.train")


def build_training(cfg, mesh, opts: RunOptions, opt_cfg: AdamWConfig,
                   batch_example: dict):
    """Returns (jitted step, init_fn, shardings)."""
    model = build_model(cfg, opts)
    train_step = make_train_step(model, opt_cfg)

    aparams = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    p_sh = planner.named(planner.plan_params(aparams, mesh), mesh)
    aopt = jax.eval_shape(adamw_init, aparams)
    o_sh = {
        "step": jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
        "master": planner.named(planner.plan_params(aopt["master"], mesh), mesh),
        "m": planner.named(planner.plan_params(aopt["m"], mesh), mesh),
        "v": planner.named(planner.plan_params(aopt["v"], mesh), mesh),
    }
    abatch = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch_example)
    b_sh = planner.named(planner.plan_batch(abatch, mesh), mesh)

    jitted = jax.jit(train_step, in_shardings=(p_sh, o_sh, b_sh),
                     out_shardings=(p_sh, o_sh, None), donate_argnums=(0, 1))

    def init_state(rng):
        params = jax.jit(model.init, out_shardings=p_sh)(rng)
        opt = jax.jit(adamw_init, out_shardings=o_sh)(params)
        return params, opt

    return jitted, init_state, (p_sh, o_sh, b_sh)


def train(cfg, *, mesh, steps: int, data_cfg: DataConfig,
          opts: RunOptions = RunOptions(), opt_cfg: AdamWConfig = AdamWConfig(),
          ckpt_dir: str | None = None, save_every: int = 0,
          log_every: int = 10) -> dict:
    from repro.kernels import autotune as kernel_autotune

    # replay persisted measured tile plans for this device before the first
    # trace (no-op on a cold cache); RunOptions.autotune / REPRO_AUTOTUNE
    # select off/replay/search
    kernel_autotune.startup(opts.autotune)
    from repro.kernels import policy as kernel_policy
    prov = kernel_autotune.provenance()
    log.info("policy %s | autotune table %s (%d tuned plan(s), %s)",
             kernel_policy.current().describe(), prov["table"],
             prov["tuned_plans"],
             "present" if prov["table_exists"] else "absent")

    ds = SyntheticLMDataset(data_cfg, cfg)
    example = ds.batch_at(0)

    with mesh, axis_rules(default_rules(mesh), mesh):
        jitted, init_state, (p_sh, o_sh, _) = build_training(
            cfg, mesh, opts, opt_cfg, example)
        params, opt_state = init_state(jax.random.key(data_cfg.seed))

        runner = None
        start = 0
        if ckpt_dir:
            mgr = CheckpointManager(ckpt_dir)
            runner = FaultTolerantRunner(mgr, save_every=save_every or steps,
                                         mesh_shape=dict(mesh.shape))
            state, start = runner.restore_or(
                {"params": params, "opt_state": opt_state},
                {"params": p_sh, "opt_state": o_sh})
            params, opt_state = state["params"], state["opt_state"]

        losses = []
        t0 = time.time()
        for step in range(start, steps):
            batch = ds.batch_at(step)
            if runner is not None:
                def do_step():
                    return jitted(params, opt_state, batch)
                params, opt_state, metrics = runner.run_step(step, do_step)
            else:
                params, opt_state, metrics = jitted(params, opt_state, batch)
            loss = float(metrics["loss"])
            losses.append(loss)
            if log_every and step % log_every == 0:
                log.info("step %d loss %.4f (%.2fs)", step, loss, time.time() - t0)
        if runner is not None:
            runner.ckpt.save_async(steps - 1, {"params": params, "opt_state": opt_state},
                                   dict(mesh.shape))
            runner.ckpt.wait()
        return {"losses": losses, "params": params, "opt_state": opt_state,
                "wall_s": time.time() - t0}


def main():
    logging.basicConfig(level=logging.INFO, format="%(message)s")
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--reduced", action="store_true", help="smoke-size config")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--save-every", type=int, default=0)
    ap.add_argument("--impl", default="",
                    help="execution-policy impl map, op=backend[,op=backend] "
                         "('*' wildcard; bare backend == '*=backend') — "
                         "replaces --attention-impl/--matmul-impl; see the "
                         "module docstring for the grammar")
    args = ap.parse_args()

    if args.impl:
        from repro.kernels import policy
        impl, variants = policy.parse_impl_spec(args.impl)
        policy.install(policy.ambient().with_(impl=impl, variants=variants))

    cfg = get_smoke_config(args.arch) if args.reduced else get_config(args.arch)
    n = len(jax.devices())
    from repro.launch.mesh import make_debug_mesh
    mesh = make_debug_mesh(n, tp=min(2, n))
    out = train(cfg, mesh=mesh, steps=args.steps,
                data_cfg=DataConfig(global_batch=args.batch, seq_len=args.seq),
                opts=RunOptions(),
                ckpt_dir=args.ckpt_dir, save_every=args.save_every)
    print(f"final loss {out['losses'][-1]:.4f} (first {out['losses'][0]:.4f}) "
          f"in {out['wall_s']:.1f}s")


if __name__ == "__main__":
    main()
