"""Continuous-batching serving engine: PWS-disciplined slot scheduling over
per-row KV decode.

The lockstep server (``repro.launch.serve``) decodes a fixed wave of
requests at one shared position: rows that finish early burn decode steps
until the slowest request in the wave ends, and a new wave cannot start
until the old one drains.  This engine removes both stalls:

* **Per-row KV lengths.**  Every decode step runs the whole ``max_batch``
  at once, but each slot carries its OWN position — the flash-decode
  kernel's per-row ``q_offset``/``kv_len`` SMEM vectors (see
  ``repro.kernels.flash_attention``) mask each lane's cache prefix
  independently, so caches at different depths coexist in one launch, and
  the traced vectors keep the no-recompile property across steps of
  varying per-row lengths.

* **Slot reuse.**  A request that hits EOS / ``max_new`` / the cache
  capacity releases its slot immediately (an *eviction*); the next queued
  request is admitted into it without waiting for the rest of the batch.

* **Batched chunked prefill.**  Prompts stream into the cache in
  fixed-size chunks (``prefill_chunk`` on the model), and every prefilling
  slot advances each iteration through at most TWO padded full-batch
  launches — one for first chunks (modality frontends / int8 scale
  calibration run there), one for continuations — with per-row ``(b,)``
  offsets and valid-token ``lens`` (0 parks a row).  Chunks interleave
  with decode steps so a long prompt never stalls in-flight rows.

* **PWS slot scheduling.**  Admission is the paper's §4.7 priority-matching
  discipline, run through the same ``core.pws.match_round`` the simulated
  machine's scheduler uses: queued requests are stealable tasks, idle slots
  are thieves, priority = work remaining (prompt tokens still to prefill +
  tokens still to generate — the size-based order).  Rounds are
  deterministic, match at most ``p - 1`` requests of the round's priority
  (Obs. 4.3, asserted), and round priorities are non-increasing within a
  drain (asserted).  The scheduler's match/steal/eviction counters are the
  engine's telemetry.

* **Eviction under memory pressure.**  An optional ``cache_budget`` (total
  live context tokens across slots, a host-mirrored high-water mark)
  bounds cache occupancy: while over budget with more than one active
  slot, the largest-context slot is evicted and its request re-queued with
  its generated tokens folded into the prompt (greedy decode makes the
  replay token-identical), re-entering through the same ``match_round``
  admission at work-remaining priority.

The engine serves EVERY model family that implements the DecodeCache
serving contract (``init_cache`` -> ``repro.models.cache`` layouts,
``prefill_chunk``, per-row ``decode_step``) — dense, hybrid, ssm, vlm,
audio; a family missing a method fails construction with a structured
``UnsupportedFamilyError``.

**Failure model** (mirrors ``repro.runtime.fault_tolerance``'s (a)/(b)/(c)
taxonomy, mapped onto launches):

(a) *Hard launch failure.*  A decode or prefill launch that exhausts its
    ``FaultPolicy`` bounded retries raises ``LaunchFailedError`` out of
    :meth:`Engine.run` for a job-level restart —
    :meth:`Engine.restart` rebuilds a replica on a (possibly shrunken)
    mesh from the latest params checkpoint via
    ``repro.runtime.elastic.serving_restore`` (pure PWS re-plan, no
    per-tensor migration; caches rebuild empty and requests replay).

(b) *Transient launch fault / poisoned row.*  Every launch runs under
    bounded retry — of faults only: an injected one or an error the device
    reports at run time; a program error (a trace or compile failure)
    propagates at once — with exponential backoff and seeded jitter (the one
    sanctioned nondeterminism — it perturbs wall time, never tokens;
    retries are sound because faults fire BEFORE the launch commits its
    donated buffers).  A row whose logits go non-finite is bisected by the
    per-row validity vector the decode step returns: only the poisoned
    slot is evicted and its request re-queued through ``match_round`` —
    token emission for that step is suppressed, so greedy replay (from
    the last row snapshot when one exists, else the full effective
    prompt) keeps the request's tokens identical to a clean run.

(c) *Stragglers + graceful degradation.*  Each launch's wall time feeds a
    ``StragglerMonitor`` watchdog (z-score flagging, flagged samples
    excluded from the window); flagged launches and failed attempts both
    count as fault events.  When ``degrade_after`` events land within
    ``degrade_window`` engine iterations, the active-slot limit shrinks by
    one (existing occupants drain naturally; admission just stops filling
    the top slot), and after ``heal_after`` healthy iterations it probes
    back up one slot at a time.  Degradation changes scheduling only —
    greedy tokens stay identical.

(d) *Replica loss.*  Handled one level up.  A ``LaunchFailedError`` that
    escalates out of :meth:`Engine.run` / :meth:`Engine.step` marks the
    whole replica dead at the fleet tier: ``repro.launch.router`` salvages
    the replica's queue and in-flight requests (each with its last
    host-staged snapshot — host memory survives device loss), re-queues
    them router-wide, and spins up a replacement through
    checkpoint-streamed :meth:`Engine.restart` on a re-planned (possibly
    shrunken) mesh.  This engine owns tiers (a)–(c) only; it never
    catches its own escalation.

Row snapshots (``models.cache.snapshot_row``/``restore_row``) are taken on
a ``snapshot_every`` generated-token cadence, host-staged per request:
recovery and ``cache_budget`` pressure eviction both resume from the last
snapshot plus a short greedy replay instead of whole-residency recompute.
A deterministic ``FaultInjector`` plan (``--inject`` / ``REPRO_FAULTS``,
grammar ``decode@12=raise,prefill@3=delay:0.2,slot@2=nan_logits``) drives
the same faults through tests, the CI smoke arm, and the bench recovery
arm — recovery is asserted invisible to numerics.

Numerics contract: with greedy decoding the engine's per-request tokens are
IDENTICAL to running each request alone through the lockstep path (same
jitted model functions, write-before-attend keeps parked rows harmless) —
``tests/test_engine.py`` asserts this request-for-request: dense fp32 and
int8, hybrid, and ssm.  (Recurrent-state families are exact because parked
rows carry identity state updates; hybrid needs ``chunk`` >= the longest
prompt — the LRU h0-fold reassociates across chunk boundaries — and ssm
needs prompt/chunk lengths aligned to ``cfg.ssm_chunk``.)
"""
from __future__ import annotations

import argparse
import logging
import time
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pws
from repro.core.sharding_hints import axis_rules
from repro.launch.serve import Request, Server
from repro.models import cache as dcache
from repro.models.base import Model, RunOptions, UnsupportedFamilyError
from repro.runtime.fault_tolerance import (
    RETRYABLE_FAULTS,
    FaultInjector,
    FaultPolicy,
    LaunchFailedError,
    StragglerMonitor,
    export_fault_counters,
)

log = logging.getLogger("repro.engine")


class SlotScheduler:
    """Deterministic slot↔request matcher on the PWS §4.7 round discipline.

    One :meth:`assign` call drains as many matching rounds as the idle-slot
    supply allows.  Each round goes through :func:`repro.core.pws.match_round`
    — idle slots (thieves, ranked by slot index) matched positionally to the
    queued requests holding the round's best priority (victims, by queue
    index) — then enforces and ASSERTS the paper's bounds: at most ``p - 1``
    matches per round (Obs. 4.3 at the round's priority; ``p`` = slot
    count), and non-increasing round priorities within the drain (§4.1).
    Counters double as the engine's telemetry.
    """

    def __init__(self, n_slots: int):
        self.p = max(int(n_slots), 1)
        self.counters = {
            "matches": 0,        # requests admitted into slots (steals)
            "rounds": 0,         # matching rounds run
            "evictions": 0,      # slot releases (stop / capacity)
            "pressure_evictions": 0,  # budget evictions (request re-queued)
            "drains": 0,         # router-level slot releases (migration/leave)
            "max_round_matches": 0,
            # fault-tolerance telemetry (engine-incremented)
            "retries": 0,             # launch retry attempts
            "faults_injected": 0,     # mirrored from the FaultInjector
            "slots_poisoned": 0,      # non-finite rows bisected + evicted
            "snapshots_taken": 0,     # host-staged row snapshots
            "snapshot_restores": 0,   # re-admissions resumed from a snapshot
            "stragglers": 0,          # watchdog-flagged slow launches
            "degradations": 0,        # active-slot-limit shrinks
            "degraded_iters": 0,      # iterations run below full slot count
        }

    def assign(self, idle_slots, queue, priority):
        """Match ``idle_slots`` to entries of ``queue`` (a sequence of
        requests; ``priority(r)`` = work remaining).  Returns the matches as
        ``[(slot, queue_index), ...]`` in match order; the caller admits and
        pops.  Deterministic in its inputs."""
        bound = max(self.p - 1, 1)
        idle = [(s, s) for s in sorted(idle_slots)]
        taken: set[int] = set()
        assignments: list[tuple[int, int]] = []
        last_best: Optional[int] = None
        while idle:
            heads = [(i, priority(r)) for i, r in enumerate(queue)
                     if i not in taken]
            best, pairs = pws.match_round(idle, heads)
            if best is None:
                break
            # Obs. 4.3: at most p-1 tasks of the round's priority are stolen
            pairs = pairs[:bound]
            assert len(pairs) <= bound, \
                "PWS bounded-steals-per-round invariant violated"
            assert last_best is None or best <= last_best, \
                "PWS round priorities must be non-increasing"
            last_best = best
            self.counters["rounds"] += 1
            self.counters["max_round_matches"] = max(
                self.counters["max_round_matches"], len(pairs))
            for pair, qidx in pairs:
                idle.remove(pair)
                taken.add(qidx)
                assignments.append((pair[1], qidx))
                self.counters["matches"] += 1
        return assignments


@dataclass
class _Slot:
    """One decode lane of the fixed-size batch."""
    req: Optional[Request] = None
    state: str = "empty"      # empty | prefill | decode
    filled: int = 0           # cache positions written (prefill progress)
    pos: int = 0              # next decode position (== tokens in context)
    last_token: int = 0
    # engine iteration of the slot's last progress (admission, chunk, or
    # decoded token) — the recency stamp the "coldest" eviction policy keys on
    last_step: int = -1
    # the residency's effective prompt: the request's prompt plus any
    # tokens generated before a pressure eviction (replayed on re-admit)
    prompt: Optional[np.ndarray] = None
    stats: dict = field(default_factory=dict)

    @property
    def context(self) -> int:
        """Live cache tokens this slot holds (budget accounting)."""
        return self.pos if self.state == "decode" else self.filled


class Engine(Server):
    """Continuous-batching engine over the lockstep :class:`Server`'s model
    setup (same jitted prefill/decode; adds the per-row decode step and the
    batched chunked-prefill step).  Serves every family implementing the
    DecodeCache contract; ``cache_budget`` (total live context tokens) turns
    on eviction under memory pressure."""

    def __init__(self, cfg, mesh, *, max_batch: int = 4, max_len: int = 256,
                 chunk: int = 16, eos_id: Optional[int] = None,
                 cache_budget: Optional[int] = None,
                 evict_policy: str = "largest",
                 fault_policy: Optional[FaultPolicy] = None,
                 injector: Optional[FaultInjector] = None,
                 snapshot_every: int = 16,
                 degrade_after: int = 3, degrade_window: int = 8,
                 heal_after: int = 16,
                 opts: RunOptions = RunOptions()):
        super().__init__(cfg, mesh, max_batch=max_batch, max_len=max_len,
                         opts=opts)
        for name in ("init_cache", "prefill_chunk", "decode_step"):
            impl = getattr(type(self.model), name, None)
            if impl is None or impl is getattr(Model, name, None):
                raise UnsupportedFamilyError(cfg.family, name)
        self.chunk = int(chunk)
        self.eos_id = eos_id
        self.cache_budget = cache_budget
        if evict_policy not in ("largest", "coldest"):
            raise ValueError(f"unknown evict_policy {evict_policy!r}: "
                             "expected 'largest' or 'coldest'")
        self.evict_policy = evict_policy
        self.fault_policy = fault_policy or FaultPolicy()
        self.injector = FaultInjector.from_env() if injector is None \
            else injector
        self.snapshot_every = int(snapshot_every)
        self.degrade_after = int(degrade_after)
        self.degrade_window = int(degrade_window)
        self.heal_after = int(heal_after)
        # per-launch watchdog: wall-time z-scores over the dispatch window
        # (injected delays sleep inside it); flagged launches count toward
        # the degradation window.  On-device stalls past dispatch need a
        # block_until_ready probe — out of scope on this backend.
        self.watchdog = StragglerMonitor(window=32, k_sigma=4.0,
                                         min_samples=5)
        self.scheduler = SlotScheduler(max_batch)
        # host-side staging for modality-frontend inputs (VLM/audio): one
        # full-batch buffer per spec, rows written at admission and shipped
        # with every first-chunk launch
        specs = self.model.batch_extras_specs(max_batch, max_len)
        self._extras_host = {
            k: np.zeros(s.shape, s.dtype) for k, s in specs.items()
        } or None

        from repro.kernels import autotune as kernel_autotune
        from repro.kernels import policy as kernel_policy
        prov = kernel_autotune.provenance()
        log.info("engine policy %s | autotune table %s (%d tuned plan(s), "
                 "%s) | faults %s | retry max=%d snapshot_every=%d",
                 kernel_policy.current().describe(), prov["table"],
                 prov["tuned_plans"],
                 "present" if prov["table_exists"] else "absent",
                 self.injector.describe(), self.fault_policy.max_retries,
                 self.snapshot_every)

        def decode_rows(params, tokens, pos, cache, poison):
            logits, cache = self.model.decode_step(params, tokens, pos, cache)
            # injected poison lands here (a traced mask — no recompile);
            # the per-row finiteness vector is the bisection signal the
            # host uses to evict exactly the corrupt slot
            logits = jnp.where(poison[:, None], jnp.float32(jnp.nan), logits)
            ok = jnp.all(jnp.isfinite(logits), axis=-1)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return nxt, ok, cache

        def chunk_step(params, tokens, offset, lens, cache, extras, *, first):
            logits, cache = self.model.prefill_chunk(
                params, tokens, offset, cache, first=first, lens=lens,
                extras=extras)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return nxt, cache

        import functools
        self._decode_rows = jax.jit(decode_rows, donate_argnums=(3,))
        self._chunk_first = jax.jit(
            functools.partial(chunk_step, first=True), donate_argnums=(4,))
        self._chunk_cont = jax.jit(
            functools.partial(chunk_step, first=False), donate_argnums=(4,))
        # each launch compiles ONCE for the engine's fixed shapes, outside
        # the failure model (see :meth:`_program`); name -> executable
        self._programs: dict = {}
        self.compile_s: dict[str, float] = {}
        self.begin([])  # stats()/adopt() are valid before the first run

    @classmethod
    def restart(cls, cfg, mesh, ckpt_dir, **kw):
        """Failure model (a): rebuild a serving replica on ``mesh`` — the
        same mesh, or a shrunken one after losing hosts — with params from
        the latest checkpoint via ``elastic.serving_restore``.  The PWS
        planner is deterministic in the mesh, so this is a pure re-plan +
        device_put: no per-tensor migration, and the restored replica's
        logits are identical to the original's.  Caches rebuild empty;
        in-flight requests re-enter through admission and replay."""
        from repro.checkpoint import CheckpointManager
        from repro.runtime import elastic

        eng = cls(cfg, mesh, **kw)
        aparams = jax.eval_shape(lambda: eng.model.init(jax.random.key(0)))
        with mesh, axis_rules(eng.rules, mesh):
            step, params, _ = elastic.serving_restore(
                CheckpointManager(ckpt_dir), aparams, mesh)
        eng.params = params
        log.info("engine restarted from step-%d checkpoint on mesh %s",
                 step, dict(mesh.shape))
        return eng

    # -- scheduling ----------------------------------------------------------
    @staticmethod
    def _effective_prompt(req: Request) -> np.ndarray:
        """The token sequence a residency must prefill: the prompt, plus —
        after a pressure eviction — every token already generated (greedy
        decode replays them deterministically)."""
        prompt = np.asarray(req.prompt, np.int32)
        if req.out:
            prompt = np.concatenate([prompt,
                                     np.asarray(req.out, np.int32)])
        return prompt

    @staticmethod
    def _work_remaining(req: Request, filled: int = 0) -> int:
        """The PWS priority: context tokens still to prefill plus tokens
        still to generate — larger tasks first, the size-based order."""
        return ((len(req.prompt) + len(req.out) - filled)
                + (req.max_new - len(req.out)))

    def _evict(self, i: int):
        self.slots[i] = _Slot()
        self.scheduler.counters["evictions"] += 1

    # -- compiled launches ---------------------------------------------------
    _LAUNCHES = {"decode": "_decode_rows", "prefill_first": "_chunk_first",
                 "prefill_cont": "_chunk_cont"}

    def _program(self, name: str, args):
        """The compiled executable of launch ``name``, built on first use.
        Compiling happens here, outside :meth:`_launch`'s retry loop: a
        trace, lowering or compile error (a kernel the chip's compiler
        refuses, a program that does not fit device memory) is a program
        error and propagates at once — it is never retried, and never
        reaches the fleet tier as a replica death.  The executable accepts
        only the shapes and shardings it was compiled for, so serving can
        never silently recompile."""
        prog = self._programs.get(name)
        if prog is None:
            t0 = time.perf_counter()
            prog = getattr(self, self._LAUNCHES[name]).lower(*args).compile()
            self.compile_s[name] = time.perf_counter() - t0
            self._programs[name] = prog
        return prog

    def _launch_args(self, name: str):
        """Parked (all-rows-idle) arguments of launch ``name`` at the
        engine's fixed shapes — what :meth:`compile` lowers with."""
        b = self.max_batch
        if name == "decode":
            return (self.params, jnp.zeros((b, 1), jnp.int32),
                    jnp.zeros((b,), jnp.int32), self.cache,
                    jnp.zeros((b,), bool))
        extras = None
        if name == "prefill_first" and self._extras_host is not None:
            extras = {k: jnp.asarray(v) for k, v in self._extras_host.items()}
        return (self.params, jnp.zeros((b, self.chunk), jnp.int32),
                jnp.zeros((b,), jnp.int32), jnp.zeros((b,), jnp.int32),
                self.cache, extras)

    def compile(self) -> dict[str, float]:
        """Compile all three launches ahead of serving (nothing runs).
        Returns the compile seconds per launch."""
        with self.mesh, axis_rules(self.rules, self.mesh):
            for name in self._LAUNCHES:
                self._program(name, self._launch_args(name))
        return dict(self.compile_s)

    # -- fault handling ------------------------------------------------------
    def _launch(self, kind: str, name: str, *args):
        """Run one compiled launch under the failure model: the injector may
        raise or delay it, faults retry up to ``FaultPolicy.max_retries``
        with seeded exponential backoff, and the watchdog z-scores its wall
        time.  Only faults retry — an injected one, or an error the device
        reports at run time (``RETRYABLE_FAULTS``); anything else is a
        program error and propagates on the first attempt.  Retrying the
        same arguments is sound because faults fire before the launch
        commits its donated buffers.  Exhausted retries escalate as
        :class:`LaunchFailedError` (failure model (a))."""
        t_start = time.time()
        prog = self._program(name, args)
        ordinal = self._launch_seq[kind]
        self._launch_seq[kind] = ordinal + 1
        counters = self.scheduler.counters
        last: Optional[BaseException] = None
        for attempt in range(self.fault_policy.max_retries + 1):
            if attempt:
                counters["retries"] += 1
                time.sleep(self.fault_policy.backoff(attempt - 1,
                                                     self._fault_rng))
            # the watchdog sees a launch's wall time as a jit call would
            # show it: a first launch includes its compile
            t0 = t_start if attempt == 0 else time.time()
            try:
                self.injector.before_launch(kind, ordinal)
                out = prog(*args)
            except RETRYABLE_FAULTS as e:
                last = e
                self._note_fault()
                log.warning("%s launch %d attempt %d failed: %r",
                            kind, ordinal, attempt, e)
                continue
            if self.watchdog.observe(time.time() - t0):
                counters["stragglers"] += 1
                self._note_fault()
                log.warning("straggler %s launch %d", kind, ordinal)
            return out
        raise LaunchFailedError(kind, ordinal,
                                self.fault_policy.max_retries + 1) from last

    def _note_fault(self):
        """One fault event (failed attempt, straggler, poisoned row) lands
        in the degradation window."""
        self._recent_faults.append(self._iter)
        self._last_fault_iter = self._iter

    def _update_degradation(self):
        """Failure model (c): shrink the active-slot limit after
        ``degrade_after`` fault events inside ``degrade_window`` iterations
        (occupied slots above the limit drain naturally — only admission
        shrinks), probe back up one slot per ``heal_after`` healthy
        iterations.  Scheduling-only: greedy tokens are unaffected."""
        counters = self.scheduler.counters
        cutoff = self._iter - self.degrade_window
        self._recent_faults = [t for t in self._recent_faults if t >= cutoff]
        if (len(self._recent_faults) >= self.degrade_after
                and self._active_limit > 1):
            self._active_limit -= 1
            self._recent_faults.clear()  # fresh evidence before the next cut
            counters["degradations"] += 1
            log.warning("degraded to %d/%d active slots",
                        self._active_limit, self.max_batch)
        elif (self._active_limit < self.max_batch
                and self._iter - self._last_fault_iter >= self.heal_after):
            self._active_limit += 1
            self._last_fault_iter = self._iter  # one probe per heal window
        if self._active_limit < self.max_batch:
            counters["degraded_iters"] += 1
        self._iter += 1

    def _poisoned(self, i: int):
        """Failure model (b), after bisection: slot ``i``'s row went
        non-finite.  Only this slot is evicted; its request re-queues
        through ``match_round`` and resumes from its last snapshot (or a
        full effective-prompt replay) — its emitted tokens stay exactly the
        clean run's."""
        req = self.slots[i].req
        self.slots[i] = _Slot()
        self.queue.append(req)
        self.scheduler.counters["slots_poisoned"] += 1
        self._note_fault()
        log.warning("poisoned slot %d: evicted uid=%d for replay", i,
                    req.uid)

    def _take_snapshot(self, i: int):
        """Host-stage row ``i`` as its request's resume point (cadence:
        every ``snapshot_every`` generated tokens)."""
        s = self.slots[i]
        self._snaps[s.req.uid] = {
            "row": dcache.snapshot_row(self.cache, i),
            "pos": s.pos, "n_out": len(s.req.out), "last": s.last_token,
        }
        self.scheduler.counters["snapshots_taken"] += 1

    def _emit(self, i: int, tok: int) -> bool:
        """Record one generated token for slot ``i``; returns True (and
        evicts) when the request stops: max_new reached, EOS, or the cache
        capacity exhausted."""
        slot = self.slots[i]
        r = slot.req
        r.out.append(tok)
        # slot.pos is the NEXT write position: at max_len the cache is full
        stop = (len(r.out) >= r.max_new
                or (self.eos_id is not None and tok == self.eos_id)
                or slot.pos >= self.max_len)
        if stop:
            self._completed.append(r)
            self._snaps.pop(r.uid, None)  # resume point no longer needed
            self._evict(i)
        return stop

    # -- engine loop ---------------------------------------------------------
    def _admit(self):
        # degradation shrinks the admissible slot range; occupants above the
        # limit keep running until they finish on their own
        idle = [i for i, s in enumerate(self.slots[:self._active_limit])
                if s.state == "empty"]
        if not idle or not self.queue:
            return
        matched = self.scheduler.assign(idle, self.queue,
                                        self._work_remaining)
        # pop in descending queue order so earlier indices stay valid
        for slot_id, qidx in sorted(matched, key=lambda m: -m[1]):
            req = self.queue.pop(qidx)
            snap = self._snaps.get(req.uid)
            if snap is not None:
                # resume from the last row snapshot: restore the row slices
                # wholesale (cursors, slabs, scales, recurrent state +
                # validity), truncate the output back to the snapshot point,
                # and replay the short greedy tail — no prefill at all
                self.cache = dcache.restore_row(self.cache, slot_id,
                                                snap["row"])
                del req.out[snap["n_out"]:]
                self.slots[slot_id] = _Slot(req=req, state="decode",
                                            filled=snap["pos"],
                                            pos=snap["pos"],
                                            last_token=snap["last"],
                                            last_step=self._iter)
                self.scheduler.counters["snapshot_restores"] += 1
                continue
            self.slots[slot_id] = _Slot(req=req, state="prefill", filled=0,
                                        last_step=self._iter,
                                        prompt=self._effective_prompt(req))
            # the row's per-row lengths/validity reset here; slabs are NOT
            # zeroed — write-before-attend makes stale tokens unreachable
            self.cache = dcache.reset_row(self.cache, slot_id)
            if self._extras_host is not None and req.extras:
                for key, val in req.extras.items():
                    self._extras_host[key][slot_id] = val

    def _advance_prefill(self):
        """Advance EVERY prefilling slot by one fixed-size chunk, batched:
        one padded full-batch launch for first chunks (all at offset 0 —
        modality frontends and int8 scale calibration run there, masked to
        live rows) and one for continuations, each with per-row offsets and
        valid-token ``lens`` (0 parks a row: decode lanes park at ``pos``,
        so their garbage writes land where their own next token lands
        first).  A slot whose chunk finishes its prompt flips to decode
        with the first generated token in hand."""
        first = [i for i, s in enumerate(self.slots)
                 if s.state == "prefill" and s.filled == 0]
        cont = [i for i, s in enumerate(self.slots)
                if s.state == "prefill" and s.filled > 0]
        for group, name in ((first, "prefill_first"),
                            (cont, "prefill_cont")):
            if not group:
                continue
            toks = np.zeros((self.max_batch, self.chunk), np.int32)
            offset = np.zeros((self.max_batch,), np.int32)
            lens = np.zeros((self.max_batch,), np.int32)
            for i, s in enumerate(self.slots):
                if i in group:
                    end = min(s.filled + self.chunk, len(s.prompt))
                    toks[i, :end - s.filled] = s.prompt[s.filled:end]
                    offset[i] = s.filled
                    lens[i] = end - s.filled
                else:  # park: overwritten before anything attends it
                    offset[i] = s.context
            extras = None
            if name == "prefill_first" and self._extras_host is not None:
                extras = {k: jnp.asarray(v)
                          for k, v in self._extras_host.items()}
            nxt, self.cache = self._launch(
                "prefill", name, self.params, jnp.asarray(toks),
                jnp.asarray(offset), jnp.asarray(lens), self.cache, extras)
            nxt = np.asarray(nxt)
            self._n_chunks += 1
            self._n_chunk_rows += len(group)
            for i in group:
                slot = self.slots[i]
                slot.filled += int(lens[i])
                slot.last_step = self._iter
                if slot.filled >= len(slot.prompt):
                    slot.state = "decode"
                    slot.pos = len(slot.prompt)
                    self.cache = dcache.set_row_valid(self.cache, i, True)
                    tok = int(nxt[i])
                    slot.last_token = tok
                    self._emit(i, tok)

    def _decode_step(self):
        """One batched per-row decode step over every decoding slot.  Rows
        not decoding still ride along (fixed shapes — no recompile): their
        garbage k/v writes park at the next position their own prefill (or
        admission) will overwrite before anything attends it — the
        write-before-attend discipline that makes lane coexistence safe.
        The step returns a per-row validity vector; a decoding row that
        comes back non-finite is bisected and evicted (:meth:`_poisoned`)
        with its token suppressed, and surviving rows snapshot on the
        ``snapshot_every`` cadence."""
        toks = np.zeros((self.max_batch, 1), np.int32)
        pos = np.zeros((self.max_batch,), np.int32)
        decoding = []
        for i, s in enumerate(self.slots):
            if s.state == "decode":
                toks[i, 0] = s.last_token
                pos[i] = s.pos
                decoding.append(i)
            else:  # park: overwritten by the slot's next prefill chunk
                pos[i] = s.context
        poison = np.zeros((self.max_batch,), bool)
        poison[self.injector.poison_rows(decoding)] = True
        nxt, ok, self.cache = self._launch(
            "decode", "decode", self.params, jnp.asarray(toks),
            jnp.asarray(pos), self.cache, jnp.asarray(poison))
        nxt, ok = np.asarray(nxt), np.asarray(ok)
        self._n_decode_steps += 1
        for i in decoding:
            if not ok[i]:
                self._poisoned(i)
                continue
            s = self.slots[i]
            s.pos += 1
            s.last_step = self._iter
            tok = int(nxt[i])
            s.last_token = tok
            if (not self._emit(i, tok) and self.snapshot_every
                    and len(s.req.out) % self.snapshot_every == 0):
                self._take_snapshot(i)

    def _apply_pressure(self):
        """Evict while the host-mirrored live-context total exceeds
        ``cache_budget`` and more than one slot is active.  The victim is
        the ``evict_policy`` pick — ``largest`` (default): the
        largest-context slot, the budget-greedy choice; ``coldest``: the
        least-recently-progressed slot by its ``last_step`` stamp, the
        recency choice that spares hot decode lanes.  Either way the
        request re-queues with generated tokens folded into the prompt
        (replayed exactly under greedy decode) — or, when the request
        holds a row snapshot, resumes from it at re-admission
        (host-staged, so it costs no budget).  A lone active slot never
        evicts — progress is guaranteed whatever the budget."""
        if self.cache_budget is None:
            return
        while True:
            active = [(s.context, i) for i, s in enumerate(self.slots)
                      if s.state != "empty"]
            if (len(active) <= 1
                    or sum(c for c, _ in active) <= self.cache_budget):
                return
            if self.evict_policy == "coldest":
                _, victim = min((self.slots[i].last_step, i)
                                for _, i in active)
            else:
                _, victim = max(active)
            req = self.slots[victim].req
            self.slots[victim] = _Slot()
            self.queue.append(req)
            self.scheduler.counters["pressure_evictions"] += 1

    # -- step API (the fleet tier's seam) ------------------------------------
    def begin(self, requests: list[Request] = ()):
        """Start a serving run: reset per-run scheduler/cache/fault state
        and queue ``requests``.  ``begin``/``step``/``busy``/``finish`` are
        the seam the fleet tier (``repro.launch.router``) drives — it
        interleaves :meth:`step` across replicas and moves requests between
        them with :meth:`drain_slot`/:meth:`adopt`/:meth:`salvage`;
        :meth:`run` composes the same four calls for the single-replica
        path."""
        self.queue: list[Request] = list(requests)
        self.scheduler = SlotScheduler(self.max_batch)  # per-run telemetry
        self.slots = [_Slot() for _ in range(self.max_batch)]
        self.cache = self.model.init_cache(self.max_batch, self.max_len)
        self._completed: list[Request] = []
        self._n_chunks = self._n_decode_steps = self._n_chunk_rows = 0
        # fault state is per-run: launch ordinals restart (so a plan's
        # decode@N names the N-th launch of THIS run), the backoff rng
        # re-seeds (reproducible delay sequence), snapshots/degradation
        # start clean
        self._launch_seq = {"decode": 0, "prefill": 0}
        self._injected_before = self.injector.counters["faults_injected"]
        self._fault_rng = self.fault_policy.make_rng()
        self._snaps: dict[int, dict] = {}
        self._recent_faults: list[int] = []
        self._iter = 0
        self._last_fault_iter = -(10 ** 9)
        self._active_limit = self.max_batch
        self.busy_s = 0.0
        self._t0 = time.time()

    def busy(self) -> bool:
        """True while this replica still owes work: queued requests or any
        occupied slot."""
        return bool(self.queue) or any(s.state != "empty"
                                       for s in self.slots)

    def step(self):
        """One engine iteration: admit, batched prefill chunks, batched
        per-row decode, pressure eviction, degradation bookkeeping.  Wall
        time accrues to this replica's ``busy_s`` clock — in production
        each replica is its own accelerator, so the fleet makespan is the
        max of these clocks, which is how the router reports fleet
        throughput when replicas time-share one test device."""
        t0 = time.time()
        with self.mesh, axis_rules(self.rules, self.mesh):
            self._admit()
            self._advance_prefill()
            if any(s.state == "decode" for s in self.slots):
                self._decode_step()
            self._apply_pressure()
            self._update_degradation()
        self.busy_s += time.time() - t0

    def finish(self) -> dict:
        """Seal the run's counters (the injected-fault mirror lands in the
        telemetry) and return the final :meth:`stats` view."""
        self.scheduler.counters["faults_injected"] = (
            self.injector.counters["faults_injected"]
            - self._injected_before)
        return self.stats()

    def stats(self) -> dict:
        """The engine's structured observability surface — scheduler
        counters, fault counters (``runtime.fault_tolerance`` keys), the
        degradation-window state, slot occupancy, and the remaining-work
        load signal.  The router's health scoring and load shedding read
        THIS, never private attributes; live mid-run reads are supported
        (the injected-fault mirror refreshes here)."""
        counters = self.scheduler.counters
        counters["faults_injected"] = (
            self.injector.counters["faults_injected"]
            - self._injected_before)
        faults = export_fault_counters(counters)
        return {
            "scheduler": {k: v for k, v in counters.items()
                          if k not in faults},
            "faults": faults,
            "degradation": {
                "active_limit": self._active_limit,
                "max_batch": self.max_batch,
                "degraded": self._active_limit < self.max_batch,
                "recent_fault_events": len(self._recent_faults),
                "iter": self._iter,
            },
            "occupancy": {
                "queued": len(self.queue),
                "prefilling": sum(s.state == "prefill" for s in self.slots),
                "decoding": sum(s.state == "decode" for s in self.slots),
                "free": sum(s.state == "empty" for s in self.slots),
            },
            "work_remaining": self.work_remaining_total(),
            "launches": dict(self._launch_seq),
            "busy_s": self.busy_s,
        }

    # -- fleet-tier request movement -----------------------------------------
    def work_remaining_total(self) -> int:
        """Queued + in-flight work remaining — the router's load signal
        (same units as the PWS admission priority)."""
        w = sum(self._work_remaining(r) for r in self.queue)
        for s in self.slots:
            if s.req is not None:
                w += self._work_remaining(s.req, s.context)
        return w

    def drain_slot(self, i: int, fresh: bool = True) -> \
            tuple[Request, Optional[dict]]:
        """Fleet-tier release of slot ``i`` (migration or replica leave):
        frees the slot and returns ``(request, resume_snapshot_or_None)``.
        A live drain of a decoding row stages a FRESH snapshot first, so
        migration never rolls the request behind its current position —
        without that, a migration per round could re-lose exactly the
        token each round gains (no fleet progress).  ``fresh=False`` is
        the death path (``salvage``): the device may be gone, so re-entry
        falls back to the last cadence snapshot (plus the post-snapshot
        greedy tail) or, with none staged, replays the effective prompt —
        token-exact either way."""
        s = self.slots[i]
        req = s.req
        if fresh and s.state == "decode":
            self._take_snapshot(i)
        snap = self._snaps.pop(req.uid, None)
        self.slots[i] = _Slot()
        self.scheduler.counters["drains"] += 1
        return req, snap

    def withdraw_queued(self, qidx: int) -> tuple[Request, Optional[dict]]:
        """Fleet-tier removal of queued request ``qidx`` (rebalancing): no
        cache state moves — just the request and any staged snapshot it
        carries from an earlier residency."""
        req = self.queue.pop(qidx)
        return req, self._snaps.pop(req.uid, None)

    def adopt(self, req: Request, snap: Optional[dict] = None):
        """Accept a request routed (or migrated) to this replica.  ``snap``
        is a host-staged resume entry whose row may have been captured on a
        DIFFERENT replica — row slices carry no slot or replica identity,
        but the layout must match, so it is validated against this
        engine's cache before staging."""
        if snap is not None:
            dcache.snapshot_compatible(self.cache, snap["row"])
            self._snaps[req.uid] = snap
        self.queue.append(req)

    def salvage(self) -> list[tuple[Request, Optional[dict]]]:
        """Everything this replica still owes, for router-wide re-queue
        after a death or a leave: queued then slotted requests, each with
        its last host-staged snapshot when one exists (host memory
        survives device loss).  Leaves the engine empty."""
        out = [(r, self._snaps.pop(r.uid, None)) for r in self.queue]
        self.queue = []
        for i, s in enumerate(self.slots):
            if s.req is not None:
                out.append(self.drain_slot(i, fresh=False))
        return out

    def run(self, requests: list[Request]) -> dict:
        """Serve ``requests`` to completion with continuous batching; greedy
        decode.  Returns wall/tokens/telemetry; per-request tokens land in
        ``request.out`` (identical to running each request alone through the
        lockstep path)."""
        self.begin(requests)
        while self.busy():
            self.step()
        stats = self.finish()
        dt = time.time() - self._t0
        n_tokens = sum(len(r.out) for r in requests)
        return {
            "wall_s": dt,
            "busy_s": self.busy_s,
            "tokens": n_tokens,
            "tok_per_s": n_tokens / max(dt, 1e-9),
            "decode_steps": self._n_decode_steps,
            "prefill_chunks": self._n_chunks,
            "prefill_chunk_rows": self._n_chunk_rows,
            "completed": {r.uid: len(r.out) for r in self._completed},
            "telemetry": dict(self.scheduler.counters),
            "stats": stats,
        }


def lockstep_tokens(engine: Engine, r: Request, *,
                    logits_at: Optional[int] = None):
    """Request ``r`` run ALONE through the lockstep jitted path: its greedy
    tokens, and — with ``logits_at`` a step index — the (V,) logits that
    step's token was drawn from (else None)."""
    out, kept = [], None
    batch = {"tokens": jnp.asarray(r.prompt)[None]}
    if r.extras:
        for key, val in r.extras.items():
            batch[key] = jnp.asarray(val)[None]
    with engine.mesh, axis_rules(engine.rules, engine.mesh):
        logits, cache = engine._prefill(engine.params, batch)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        for step in range(r.max_new):
            if step == logits_at:
                kept = np.asarray(logits[0], np.float32)
            tok = int(nxt[0])
            out.append(tok)
            if engine.eos_id is not None and tok == engine.eos_id:
                break
            if len(out) >= r.max_new:
                break
            pos = jnp.asarray(len(r.prompt) + step, jnp.int32)
            nxt, logits, cache = engine._decode(engine.params, nxt[:, None],
                                                pos, cache)
    return out, kept


def check_lockstep_parity(engine: Engine, requests: list[Request]) -> bool:
    """Row-for-row acceptance check: each request run ALONE through the
    lockstep jitted path must reproduce the engine's tokens exactly."""
    ok = True
    for r in requests:
        alone, _ = lockstep_tokens(engine, r)
        if alone != r.out:
            ok = False
            log.error("parity FAIL uid=%d alone=%s engine=%s", r.uid,
                      alone, r.out)
    return ok


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--prompt-align", type=int, default=1,
                    help="round generated prompt lengths up to a multiple "
                         "of N (ssm exactness needs chunk boundaries on "
                         "cfg.ssm_chunk multiples)")
    ap.add_argument("--cache-budget", type=int, default=0,
                    help="total live context tokens across slots before "
                         "pressure eviction kicks in (0 = unbounded)")
    ap.add_argument("--evict-policy", default="largest",
                    choices=("largest", "coldest"),
                    help="pressure-eviction victim: largest context "
                         "(default) or coldest = least-recently-progressed "
                         "slot by its last-step stamp")
    ap.add_argument("--check-lockstep", action="store_true",
                    help="re-run each request alone through the lockstep "
                         "path and assert row-for-row token parity")
    ap.add_argument("--inject", default="",
                    help="deterministic fault plan, e.g. 'decode@12=raise,"
                         "prefill@3=delay:0.2,slot@2=nan_logits' (default: "
                         "the REPRO_FAULTS env plan)")
    ap.add_argument("--snapshot-every", type=int, default=16,
                    help="host-stage a row snapshot every N generated "
                         "tokens (0 = off; recovery then replays the full "
                         "effective prompt)")
    ap.add_argument("--impl", default="",
                    help="execution-policy impl map (see serve.py docstring)")
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO, format="%(message)s")
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()

    if args.impl:
        from repro.kernels import policy
        impl, variants = policy.parse_impl_spec(args.impl)
        policy.install(policy.ambient().with_(impl=impl, variants=variants))

    from repro.configs import get_config, get_smoke_config
    from repro.launch.mesh import make_debug_mesh
    cfg = get_smoke_config(args.arch) if args.reduced else get_config(args.arch)
    mesh = make_debug_mesh(tp=min(2, len(jax.devices())))
    engine = Engine(cfg, mesh, max_batch=args.slots, max_len=128,
                    chunk=args.chunk, opts=RunOptions(),
                    cache_budget=args.cache_budget or None,
                    evict_policy=args.evict_policy,
                    injector=(FaultInjector(args.inject) if args.inject
                              else None),
                    snapshot_every=args.snapshot_every)
    rng = np.random.default_rng(0)

    def plen():
        n = int(rng.integers(4, 24))
        return -(-n // args.prompt_align) * args.prompt_align

    specs = engine.model.batch_extras_specs(1, 128)

    def mk_extras():
        # one random modality-frontend row per request (VLM/audio stubs)
        return {k: rng.standard_normal(s.shape[1:]).astype(s.dtype)
                for k, s in specs.items()} or None

    reqs = [Request(i, rng.integers(3, cfg.vocab_size,
                                    plen()).astype(np.int32),
                    max_new=int(rng.integers(2, args.max_new + 1)),
                    extras=mk_extras())
            for i in range(args.requests)]
    out = engine.run(reqs)
    print(f"served {out['tokens']} tokens in {out['wall_s']:.2f}s "
          f"({out['tok_per_s']:.1f} tok/s; {out['decode_steps']} decode "
          f"steps, {out['prefill_chunks']} prefill chunks)")
    print(f"telemetry: {out['telemetry']}")
    if args.check_lockstep:
        assert check_lockstep_parity(engine, reqs), \
            "engine tokens diverge from the lockstep baseline"
        print("lockstep parity: OK")


if __name__ == "__main__":
    main()
