"""Serving across several devices, on four virtual CPU devices.

The device count is fixed when JAX starts, so each check runs in a child
process with ``--xla_force_host_platform_device_count=4``: the router must
put each replica on a device of its own, and an engine on the (2, 2) mesh —
its Pallas kernels run per shard, since XLA cannot partition a kernel —
must serve the same tokens as an engine on one device.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

ROUTER = r"""
import json, sys
import jax
from repro.configs import get_smoke_config
from repro.launch.router import Router
from repro.launch.serve import Request
import numpy as np

cfg = get_smoke_config("qwen3-1.7b")
rng = np.random.default_rng(0)
spec = [(rng.integers(3, cfg.vocab_size, int(rng.integers(4, 12))).astype(np.int32),
         int(rng.integers(2, 5))) for _ in range(4)]
router = Router(cfg, n_replicas=4, ckpt_dir=sys.argv[1], max_batch=2,
                max_len=32, chunk=8)
reqs = [Request(i, p, max_new=m) for i, (p, m) in enumerate(spec)]
router.run(reqs)
print(json.dumps({
    "devices": [[d.id for d in r.engine.mesh.devices.flat]
                for r in router.replicas],
    "done": all(len(r.out) == r.max_new for r in reqs)}))
"""

TP_ENGINE = r"""
import json
import jax
import numpy as np
from repro.configs import get_smoke_config
from repro.kernels import policy
from repro.launch.engine import Engine
from repro.launch.mesh import make_debug_mesh
from repro.launch.serve import Request

cfg = get_smoke_config("qwen3-1.7b")
rng = np.random.default_rng(1)
spec = [(rng.integers(3, cfg.vocab_size, int(rng.integers(4, 12))).astype(np.int32),
         int(rng.integers(2, 5))) for _ in range(4)]
policy.install(policy.ambient().with_(impl={"*": "pallas"}, strict_tiles=True))
outs = {}
for name, mesh in (("one", make_debug_mesh(1)), ("tp", make_debug_mesh(tp=2))):
    eng = Engine(cfg, mesh, max_batch=2, max_len=32, chunk=8)
    reqs = [Request(i, p, max_new=m) for i, (p, m) in enumerate(spec)]
    eng.run(reqs)
    outs[name] = [r.out for r in reqs]
    outs[name + "_mesh"] = dict(mesh.shape)
print(json.dumps(outs))
"""


def _run(script, *args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "") + " "
                         "--xla_force_host_platform_device_count=4"),
           "PYTHONPATH": str(ROOT / "src")}
    proc = subprocess.run([sys.executable, "-c", script, *args], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_router_puts_each_replica_on_its_own_device(tmp_path):
    out = _run(ROUTER, str(tmp_path))
    assert sorted(out["devices"]) == [[0], [1], [2], [3]]
    assert out["done"]


def test_engine_on_2x2_mesh_serves_one_device_tokens():
    out = _run(TP_ENGINE)
    assert out["tp_mesh"] == {"data": 2, "model": 2}
    assert out["one_mesh"] == {"data": 1, "model": 1}
    assert out["tp"] == out["one"]
