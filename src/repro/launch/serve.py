"""Batched serving driver: the LOCKSTEP baseline (wave-at-a-time).

Serving model: requests arrive with prompts; the server packs up to
``max_batch`` requests, prefills them (left-padded to a shared window), and
decodes in lockstep — one shared position per step — with per-row stopping:
a row that hits EOS / ``max_new`` stops appending (its lane still rides the
batch until the wave's slowest request ends — that burned work is exactly
what ``repro.launch.engine`` removes with per-row KV lengths, chunked
prefill, and PWS slot scheduling; this module stays as the simple baseline
and the parity oracle).  Parameters are placed by the PWS planner
(``core.planner.plan_params``) on the server's mesh.

Backend selection is the ambient ``repro.kernels.policy`` execution
policy's call.  The ``--impl`` flag installs a process policy with the
grammar

    --impl op=backend[,op=backend]     e.g. --impl attention=pallas
    --impl '*=pallas'                  wildcard: every op
    --impl pallas                      bare backend == '*=backend'
    --impl op=backend:knob=value       variant knobs, e.g.
                                       --impl 'attention=pallas:kv_dtype=int8'
                                       --impl 'matmul=pallas:qkv_fused=true'
                                       --impl 'matmul=pallas:backend=strassen'

where op is a registered kernel name (``scan`` | ``matmul`` | ``transpose``
| ``attention`` | ``fft``) or ``*``, and backend one of ``auto`` (registry
decides) | ``jnp`` | ``pallas``.  ``:knob=value`` suffixes set per-op
variant knobs on the policy (``attention kv_dtype=int8`` selects the
quantized KV cache; ``matmul qkv_fused=true`` fuses the QKV projections;
model matmuls always run the classical schedule, which keeps each token
row independent of its batch).  Under a pallas attention policy,
prefill
dispatches as zero-offset self-attention and decode as a cached-attention
call where the step position flows into the kernel as a traced ``q_offset``
(and, causally, the KV valid-length) — per-step positions never retrace
either jit.  ``REPRO_IMPL`` (same grammar) sets the policy without a flag.

The lockstep server has NO failure handling by design — it is the simple
baseline and the parity oracle.  Fault injection, bounded launch retry,
row snapshots, and graceful degradation live in ``repro.launch.engine``
(see its "Failure model" section); ``REPRO_FAULTS`` / ``--inject`` plans
target the engine only.  The serving stack stacks in three tiers: this
``Server`` (lockstep oracle) → ``repro.launch.engine.Engine`` (continuous
batching + single-replica fault tolerance) → ``repro.launch.router.Router``
(a data-parallel fleet of engines with randomized-stealing routing,
replica death/respawn, and elastic join/leave) — each tier's guarantee is
token-identity with the tier below it.
"""
from __future__ import annotations

import argparse
import logging
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.core import planner
from repro.core.sharding_hints import axis_rules, default_rules
from repro.models import build_model
from repro.models.base import RunOptions

log = logging.getLogger("repro.serve")


@dataclass
class Request:
    uid: int
    prompt: np.ndarray  # (plen,) int32
    max_new: int = 16
    # generated tokens; under the engine's fault/pressure recovery, `out`
    # may be truncated back to a row-snapshot point and regenerated — greedy
    # decode makes the replay token-identical, so the final contents always
    # match a clean run
    out: list = field(default_factory=list)
    # modality-frontend inputs keyed by the model's batch_extras_specs()
    # (e.g. "image_embeds" / "audio_frames"), one row each, no batch axis
    extras: dict | None = None
    # the serving engine's ``time.perf_counter`` stamps: queued (adopted, or
    # queued by ``begin``), first admitted into a slot, first token emitted;
    # a re-admission after an eviction or a move between replicas keeps them
    queued: float | None = None
    admitted: float | None = None
    first_token: float | None = None


class Server:
    def __init__(self, cfg, mesh, *, max_batch: int = 8, max_len: int = 256,
                 opts: RunOptions = RunOptions()):
        from repro.kernels import autotune as kernel_autotune
        from repro.kernels import planner as kernel_planner

        self.cfg = cfg
        self.mesh = mesh
        self.max_batch = max_batch
        self.max_len = max_len
        # serving tiles (q/kv blocks, kernel backend) resolve through the
        # kernel substrate; Server keeps the resolved copy for telemetry
        self.opts = kernel_planner.resolve_run_options(
            opts, head_dim=cfg.head_dim_, dtype=cfg.activation_dtype)
        # replay persisted measured tile plans for this device (no-op on a
        # cold cache).  Note "search" only fills the table from *eager*
        # dispatches — under jax.jit (all serving steps) it degrades to
        # replay; populate tables with benchmarks/autotune.py instead
        kernel_autotune.startup(self.opts.autotune)
        from repro.kernels import policy as kernel_policy
        prov = kernel_autotune.provenance()
        log.info("policy %s | autotune table %s (%d tuned plan(s), %s)",
                 kernel_policy.current().describe(), prov["table"],
                 prov["tuned_plans"],
                 "present" if prov["table_exists"] else "absent")
        self.model = build_model(cfg, self.opts)
        self.rules = default_rules(mesh)

        # params land where the PWS planner puts them on this mesh (as a
        # checkpoint-streamed restart places them), committed, so launches
        # compile against them in place
        key = jax.random.key(0)
        shardings = planner.named(
            planner.plan_params(jax.eval_shape(self.model.init, key), mesh),
            mesh)
        with mesh, axis_rules(self.rules, mesh):
            self.params = jax.jit(self.model.init,
                                  out_shardings=shardings)(key)

        def prefill(params, batch):
            return self.model.prefill(params, batch, max_len)

        def decode(params, tokens, pos, cache):
            logits, cache = self.model.decode_step(params, tokens, pos, cache)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return nxt, logits, cache

        self._prefill = jax.jit(prefill)
        self._decode = jax.jit(decode, donate_argnums=(3,))

    def run_batch(self, requests: list[Request],
                  eos_id: int | None = None) -> dict:
        """Prefill + greedy decode a batch of requests in lockstep, with
        per-row stop: a row stops appending once it hits ``max_new`` or
        ``eos_id``, and the wave ends early when every row is done.  Returns
        per-request completion counts alongside the wave totals."""
        b = len(requests)
        plen = max(len(r.prompt) for r in requests)
        toks = np.zeros((b, plen), np.int32)
        for i, r in enumerate(requests):
            toks[i, plen - len(r.prompt):] = r.prompt  # left-pad
        batch = {"tokens": jnp.asarray(toks)}
        mc = self.cfg
        rng = np.random.default_rng(0)
        if mc.family == "vlm":
            batch["image_embeds"] = jnp.asarray(rng.standard_normal(
                (b, mc.n_image_tokens, mc.d_model), dtype=np.float32))
        if mc.family == "audio":
            enc_len = max(int(self.max_len * mc.encoder_len_ratio), 16)
            batch["audio_frames"] = jnp.asarray(rng.standard_normal(
                (b, enc_len, mc.d_model), dtype=np.float32))

        t0 = time.time()
        done = [False] * b
        with self.mesh, axis_rules(self.rules, self.mesh):
            logits, cache = self._prefill(self.params, batch)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            max_new = max(r.max_new for r in requests)
            for step in range(max_new):
                for i, r in enumerate(requests):
                    if done[i]:
                        continue  # per-row stop: finished rows stop appending
                    tok = int(nxt[i])
                    r.out.append(tok)
                    if (len(r.out) >= r.max_new
                            or (eos_id is not None and tok == eos_id)):
                        done[i] = True
                if all(done):
                    break  # the wave drained early — skip the dead steps
                pos = jnp.asarray(plen + step, jnp.int32)
                nxt, _, cache = self._decode(self.params, nxt[:, None], pos,
                                             cache)
        dt = time.time() - t0
        n_tokens = sum(len(r.out) for r in requests)
        return {"wall_s": dt, "tokens": n_tokens,
                "tok_per_s": n_tokens / max(dt, 1e-9),
                "completed": {r.uid: len(r.out) for r in requests}}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--impl", default="",
                    help="execution-policy impl map, op=backend[,op=backend] "
                         "('*' wildcard; bare backend == '*=backend'): one "
                         "flag for every kernel-backend decision — replaces "
                         "--attention-impl/--matmul-impl (see module "
                         "docstring for the grammar)")
    args = ap.parse_args()
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()

    if args.impl:
        from repro.kernels import policy
        impl, variants = policy.parse_impl_spec(args.impl)
        policy.install(policy.ambient().with_(impl=impl, variants=variants))

    cfg = get_smoke_config(args.arch) if args.reduced else get_config(args.arch)
    from repro.launch.mesh import make_debug_mesh
    mesh = make_debug_mesh(tp=min(2, len(jax.devices())))
    server = Server(cfg, mesh, max_batch=args.batch, max_len=128,
                    opts=RunOptions())
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(3, cfg.vocab_size, rng.integers(4, 20)).astype(np.int32),
                    max_new=args.max_new) for i in range(args.batch)]
    out = server.run_batch(reqs)
    print(f"served {out['tokens']} tokens in {out['wall_s']:.2f}s "
          f"({out['tok_per_s']:.1f} tok/s)")
    for r in reqs[:2]:
        print(f"req {r.uid}: {r.out[:8]}...")


if __name__ == "__main__":
    main()
