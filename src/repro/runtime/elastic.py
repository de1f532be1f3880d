"""Elastic scaling: re-plan shardings for a changed mesh.

The PWS planner is a deterministic function of the mesh (paper Obs. 4.3:
the steal schedule is determined by p) — so scaling from 512 to 256 chips
(or onto a degraded 2x15x16 slice) is: rebuild the mesh, re-run
``plan_params``/``plan_cache``, and device_put the checkpointed logical
arrays under the new shardings.  No per-tensor migration logic.

Two restart paths share the machinery: :func:`elastic_restore` rebuilds a
TRAIN state (params + optimizer) and :func:`serving_restore` a SERVING
replica (params only — decode caches are rebuilt empty and refilled by
request replay, so a replica restarted on a shrunken mesh serves logits
identical to the original; ``repro.launch.engine.Engine.restart`` is the
engine-level wrapper).
"""
from __future__ import annotations

from typing import Any

import jax

from repro.core import planner


def replan_for_mesh(abstract_state: Any, new_mesh) -> Any:
    """Shardings for a train state {params, opt_state} on a new mesh."""
    aparams = abstract_state["params"]
    pspec = planner.named(planner.plan_params(aparams, new_mesh), new_mesh)
    opt = abstract_state["opt_state"]
    ospec = {
        "step": jax.sharding.NamedSharding(new_mesh, jax.sharding.PartitionSpec()),
        "master": planner.named(planner.plan_params(opt["master"], new_mesh), new_mesh),
        "m": planner.named(planner.plan_params(opt["m"], new_mesh), new_mesh),
        "v": planner.named(planner.plan_params(opt["v"], new_mesh), new_mesh),
    }
    return {"params": pspec, "opt_state": ospec}


def elastic_restore(ckpt_manager, abstract_state: Any, new_mesh):
    """Restore the latest checkpoint resharded onto ``new_mesh``."""
    shardings = replan_for_mesh(abstract_state, new_mesh)
    step, state = ckpt_manager.restore_latest(abstract_state, shardings)
    return step, state, shardings


def replan_params_for_mesh(abstract_params: Any, new_mesh):
    """Shardings for a params-only (serving) state on a new mesh."""
    return planner.named(planner.plan_params(abstract_params, new_mesh),
                         new_mesh)


def respawn_mesh(prev_mesh, lost_devices: int = 0):
    """The mesh a replacement replica spins up on after its predecessor
    dies: the predecessor's own devices minus ``lost_devices`` (a dead
    replica's hosts may be gone for good), re-planned through the
    debug-mesh factory so tensor-parallel stays as wide as the survivors
    allow.  Shrinking to fewer devices is always legal — the PWS planner is
    deterministic in the mesh, so the respawned replica's logits match the
    original's whatever the shape (asserted by the router tests)."""
    from repro.launch.mesh import make_debug_mesh, mesh_device_count

    n = max(mesh_device_count(prev_mesh) - int(lost_devices), 1)
    return make_debug_mesh(n, tp=min(2, n),
                           devices=list(prev_mesh.devices.flat))


def serving_restore(ckpt_manager, abstract_params: Any, new_mesh):
    """Restore the latest params checkpoint resharded onto ``new_mesh`` for
    a serving restart: no optimizer state, no cache (decode caches rebuild
    empty; in-flight requests replay through admission).  Accepts
    checkpoints saved as ``{"params": ...}`` (the train driver's layout).
    Returns ``(step, params, shardings)``."""
    shardings = replan_params_for_mesh(abstract_params, new_mesh)
    step, state = ckpt_manager.restore_latest({"params": abstract_params},
                                              {"params": shardings})
    return step, state["params"], shardings
