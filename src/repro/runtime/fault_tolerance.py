"""Fault tolerance: fault injection, bounded retry, straggler detection.

At 1000+ node scale the failure model is: (a) hard node loss -> job restart
from the latest checkpoint on a (possibly re-sized) mesh; (b) transient step
failure (preemption notice, ECC retry, link flap) -> bounded in-place retry;
(c) stragglers -> detected by per-step wall-time z-scores, mitigated by
checkpoint-and-replan (the PWS planner is deterministic in p, so dropping to
a smaller healthy mesh is a pure re-plan + elastic reshard — no manual
resharding logic).

The serving engine (``repro.launch.engine``) maps the same taxonomy onto
launches instead of train steps: (a) a launch that exhausts its retries
raises :class:`LaunchFailedError` for a job-level restart, (b) a transient
launch fault retries under :class:`FaultPolicy`'s bounded backoff, and
(c) straggler launches are flagged by the same :class:`StragglerMonitor`
z-scores and feed the engine's graceful-degradation window.

Everything here is policy-only and model-free: the runner wraps any step
callable, and :class:`FaultInjector` drives the SAME injected-fault plans
through tests, the CI smoke arm, and the bench recovery arm.  Faults fire
deterministically from a declarative plan; the one sanctioned source of
nondeterminism is the seeded retry-backoff jitter (:class:`FaultPolicy` —
the RWS companion analysis' randomized-stealing model), which perturbs
*wall time* only, never the recovered output.
"""
from __future__ import annotations

import logging
import os
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import numpy as np

log = logging.getLogger(__name__)

FAULT_PLAN_ENV = "REPRO_FAULTS"

# The engine's fault-telemetry counter names, in one place so every consumer
# (Engine.stats(), the router's health score, benches) slices the same keys
# out of the scheduler's counter dict instead of hard-coding its layout.
FAULT_COUNTER_KEYS = (
    "retries", "faults_injected", "slots_poisoned", "snapshots_taken",
    "snapshot_restores", "stragglers", "degradations", "degraded_iters",
)


def export_fault_counters(counters: dict) -> dict:
    """The fault-tolerance slice of an engine's telemetry counters (missing
    keys read as 0 — a counter dict from an older engine stays valid)."""
    return {k: counters.get(k, 0) for k in FAULT_COUNTER_KEYS}


def parse_fleet_plan(plan: str, n_replicas: int) -> list[str]:
    """Split a fleet fault plan into per-replica engine plans.  The fleet
    grammar is ``plan[|plan...]`` — ``|``-separated positional per-replica
    plans (position = replica id, missing tails empty), e.g.
    ``|decode@4=raise:99`` faults replica 1 only.  A plan with no ``|``
    applies to EVERY replica, matching single-engine semantics.  Each piece
    is validated through :func:`parse_fault_plan`."""
    if "|" in plan:
        pieces = [p.strip() for p in plan.split("|")]
        if len(pieces) > n_replicas:
            raise ValueError(
                f"fleet fault plan names {len(pieces)} replicas but the "
                f"router has {n_replicas}")
        pieces += [""] * (n_replicas - len(pieces))
    else:
        pieces = [plan] * n_replicas
    for piece in pieces:
        parse_fault_plan(piece)
    return pieces


class InjectedFault(RuntimeError):
    """A fault raised by :class:`FaultInjector` (distinguishable from real
    failures in logs; handled identically by the retry machinery)."""


# What a serving launch may retry: a fault the injector fired, or an error
# the device runtime reports while the launch executes.  A program error (a
# ValueError while tracing, a refused compile) is not in this tuple, so it
# propagates on the first attempt.
RETRYABLE_FAULTS = (InjectedFault, jax.errors.JaxRuntimeError)


class LaunchFailedError(RuntimeError):
    """A launch exhausted its bounded retries — the serving analogue of a
    hard step failure, escalated for job-level restart."""

    def __init__(self, kind: str, ordinal: int, attempts: int):
        super().__init__(
            f"{kind} launch {ordinal} failed after {attempts} attempt(s)")
        self.kind = kind
        self.ordinal = ordinal
        self.attempts = attempts


@dataclass
class FaultSpec:
    """One parsed fault-plan entry.

    ``kind``     ``decode`` | ``prefill`` (``index`` = per-run launch
                 ordinal) or ``slot`` (``index`` = engine slot id).
    ``action``   ``raise`` (fail the launch; ``arg`` = consecutive attempts
                 to fail, default 1), ``delay`` (sleep before the launch —
                 a straggler; ``arg`` = seconds, default 0.05), or
                 ``nan_logits`` (poison the slot's logits; ``arg`` = fire on
                 the n-th decode launch in which the slot is decoding,
                 default 1).
    """

    kind: str
    index: int
    action: str
    arg: float
    remaining: float = field(default=0.0)

    def __post_init__(self):
        # 'raise' burns one count per failed attempt; the others fire once
        # after 'arg' eligible launches (delay is immediate: count 1)
        self.remaining = self.arg if self.action == "raise" else (
            self.arg if self.action == "nan_logits" else 1)


_KINDS = ("decode", "prefill", "slot")
_ACTIONS = ("raise", "delay", "nan_logits")
_DEFAULT_ARG = {"raise": 1, "delay": 0.05, "nan_logits": 1}


def parse_fault_plan(plan: str) -> list[FaultSpec]:
    """Parse the declarative grammar
    ``kind@index=action[:arg][,kind@index=action[:arg]...]``, e.g.
    ``decode@12=raise,prefill@3=delay:0.2,slot@2=nan_logits``."""
    specs: list[FaultSpec] = []
    for raw in filter(None, (e.strip() for e in plan.split(","))):
        try:
            target, action = raw.split("=", 1)
            kind, index = target.split("@", 1)
            arg = None
            if ":" in action:
                action, arg_s = action.split(":", 1)
                arg = float(arg_s)
        except ValueError as e:
            raise ValueError(f"malformed fault-plan entry {raw!r} "
                             "(want kind@index=action[:arg])") from e
        if kind not in _KINDS:
            raise ValueError(f"unknown fault kind {kind!r} in {raw!r} "
                             f"(want one of {_KINDS})")
        if action not in _ACTIONS:
            raise ValueError(f"unknown fault action {action!r} in {raw!r} "
                             f"(want one of {_ACTIONS})")
        if action == "nan_logits" and kind != "slot":
            raise ValueError(f"{raw!r}: nan_logits targets a slot")
        if action in ("raise", "delay") and kind == "slot":
            raise ValueError(f"{raw!r}: {action} targets a launch "
                             "(decode/prefill)")
        specs.append(FaultSpec(kind, int(index), action,
                               _DEFAULT_ARG[action] if arg is None else arg))
    return specs


class FaultInjector:
    """Deterministic, plan-driven fault source.

    The plan (see :func:`parse_fault_plan`; ``REPRO_FAULTS`` env) names
    exactly which launches fail, which straggle, and which slot's logits go
    non-finite — so a faulted run is reproducible end to end and its
    recovered output can be asserted *token-identical* to the clean run.
    The seed jitters only the injected delay's duration (never whether or
    where a fault fires).
    """

    def __init__(self, plan: str = "", seed: int = 0):
        self.plan = plan
        self.specs = parse_fault_plan(plan)
        self.rng = np.random.default_rng(seed)
        self.counters = {"faults_injected": 0}

    @classmethod
    def from_env(cls, seed: int = 0) -> "FaultInjector":
        """An injector for the ``REPRO_FAULTS`` plan (empty plan = no-op)."""
        return cls(os.environ.get(FAULT_PLAN_ENV, ""), seed=seed)

    def __bool__(self) -> bool:
        return bool(self.specs)

    def describe(self) -> str:
        return self.plan or "none"

    def before_launch(self, kind: str, ordinal: int) -> None:
        """Fire any ``raise``/``delay`` fault planned for this launch.
        Raises :class:`InjectedFault` BEFORE the launch commits (donated
        buffers untouched), so a bounded retry of the same arguments is
        sound; a ``delay`` sleeps in the launch's timed window so the
        straggler watchdog sees it."""
        for spec in self.specs:
            if (spec.kind != kind or spec.index != ordinal
                    or spec.remaining <= 0):
                continue
            if spec.action == "raise":
                spec.remaining -= 1
                self.counters["faults_injected"] += 1
                raise InjectedFault(f"injected: {kind} launch {ordinal}")
            if spec.action == "delay":
                spec.remaining -= 1
                self.counters["faults_injected"] += 1
                # seeded jitter perturbs duration only — never the outcome
                time.sleep(spec.arg * (1.0 + 0.1 * self.rng.random()))

    def poison_rows(self, decoding_slots) -> list[int]:
        """Slot ids whose logits must go non-finite on THIS decode launch:
        each ``slot@i=nan_logits:n`` entry counts down one per decode launch
        in which slot ``i`` is decoding and fires on the n-th."""
        out = []
        for spec in self.specs:
            if (spec.kind != "slot" or spec.action != "nan_logits"
                    or spec.remaining <= 0 or spec.index not in decoding_slots):
                continue
            spec.remaining -= 1
            if spec.remaining <= 0:
                self.counters["faults_injected"] += 1
                out.append(spec.index)
        return out


@dataclass(frozen=True)
class FaultPolicy:
    """Bounded-retry policy: up to ``max_retries`` in-place retries with
    exponential backoff and seeded jitter.  The jitter is the RWS-style
    randomized arm — it decorrelates retry storms across replicas without
    touching the recovered output (launches are pure functions of their
    arguments)."""

    max_retries: int = 2
    backoff_s: float = 0.005
    backoff_mult: float = 2.0
    jitter: float = 0.5
    seed: int = 0

    def make_rng(self) -> np.random.Generator:
        return np.random.default_rng(self.seed)

    def backoff(self, attempt: int, rng: np.random.Generator) -> float:
        """Sleep before retry ``attempt`` (0-based): exponential base times
        a seeded multiplicative jitter in [1, 1 + jitter)."""
        base = self.backoff_s * (self.backoff_mult ** attempt)
        return base * (1.0 + self.jitter * float(rng.random()))


@dataclass
class StragglerMonitor:
    """Rolling per-step time stats; flags steps slower than mean + k*std.
    Flagged samples are EXCLUDED from the rolling window — a genuine
    straggler must not inflate the std and mask the next one.  On real
    pods, per-host step times arrive via the coordination service; here the
    same math runs on the local step series."""

    window: int = 50
    k_sigma: float = 3.0
    min_samples: int = 10
    times: list[float] = field(default_factory=list)
    flagged: int = 0

    def observe(self, dt: float) -> bool:
        """Returns True if this step is a straggler."""
        ts = self.times
        if len(ts) >= self.min_samples:
            mean = sum(ts) / len(ts)
            var = sum((t - mean) ** 2 for t in ts) / len(ts)
            if dt > mean + self.k_sigma * max(var ** 0.5, 1e-9):
                self.flagged += 1
                return True  # outlier: keep it OUT of the window stats
        ts.append(dt)
        if len(ts) > self.window:
            ts.pop(0)
        return False


class FaultTolerantRunner:
    """Wraps a training loop step with retry + periodic checkpointing.

    Usage::
        runner = FaultTolerantRunner(ckpt_manager, save_every=100)
        state, start = runner.restore_or(state_init, shardings)
        for step in range(start, total):
            state = runner.run_step(step, lambda: train_step(state, batch))
    """

    def __init__(self, ckpt_manager, *, save_every: int = 100,
                 max_retries: int = 2, mesh_shape: Optional[dict] = None):
        self.ckpt = ckpt_manager
        self.save_every = save_every
        self.max_retries = max_retries
        self.mesh_shape = mesh_shape or {}
        self.monitor = StragglerMonitor()
        self.retries = 0

    def restore_or(self, state_init: Any, shardings: Any = None) -> tuple[Any, int]:
        try:
            step, state = self.ckpt.restore_latest(state_init, shardings)
            log.info("restored checkpoint at step %d", step)
            return state, step + 1
        except FileNotFoundError:
            return state_init, 0

    def run_step(self, step: int, step_fn: Callable[[], Any]) -> Any:
        """Execute one step with bounded retry; checkpoint on schedule.
        ``step_fn`` closes over whatever state it needs."""
        last_exc: Optional[BaseException] = None
        for attempt in range(self.max_retries + 1):
            t0 = time.time()
            try:
                new_state = step_fn()
                dt = time.time() - t0
                if self.monitor.observe(dt):
                    log.warning("straggler step %d: %.3fs", step, dt)
                if self.save_every and (step + 1) % self.save_every == 0:
                    self.ckpt.save_async(step, new_state, self.mesh_shape)
                return new_state
            except Exception as e:  # noqa: BLE001 — deliberate: retry any step fault
                last_exc = e
                self.retries += 1
                log.warning("step %d attempt %d failed: %r", step, attempt, e)
        # out of retries: persist what we have and re-raise for job-level restart
        self.ckpt.wait()
        raise RuntimeError(f"step {step} failed after {self.max_retries} retries") from last_exc
