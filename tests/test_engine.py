"""Continuous-batching engine: numerics contract + PWS slot scheduling.

The acceptance bar for ``repro.launch.engine``: with greedy decoding the
engine's per-request tokens are IDENTICAL, request-for-request, to running
each request alone through the lockstep jitted path — dense fp32 and
int8-KV, hybrid, and ssm — with batched chunked prefill interleaved
between decode steps, pressure eviction replaying evicted requests
exactly, and the per-row decode step compiling exactly once across ragged
batch compositions.  The SlotScheduler's §4.7 round discipline (bounded
steals per round, non-increasing round priorities, deterministic matching)
is unit-tested without a model.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.kernels import policy
from repro.launch.engine import Engine, SlotScheduler, check_lockstep_parity
from repro.launch.mesh import make_debug_mesh
from repro.launch.serve import Request
from repro.models.base import Model, RunOptions, UnsupportedFamilyError


def _requests(n, *, seed=0, max_prompt=20, max_new=8, vocab=256, align=1):
    """Mixed-length workload: ragged prompts, skewed generation budgets.
    ``align`` rounds prompt lengths up to a multiple (ssm exactness needs
    chunk boundaries on ``cfg.ssm_chunk`` multiples)."""
    rng = np.random.default_rng(seed)

    def plen():
        n_ = int(rng.integers(4, max_prompt))
        return -(-n_ // align) * align

    return [Request(i, rng.integers(3, vocab, plen()).astype(np.int32),
                    max_new=int(rng.integers(2, max_new + 1)))
            for i in range(n)]


@pytest.fixture(scope="module")
def mesh():
    return make_debug_mesh(tp=min(2, len(jax.devices())))


@pytest.fixture(autouse=True)
def _clear_autotune_pin():
    """Server.__init__ runs the launcher's ``autotune.startup``, which pins
    the mode process-wide — clear it so later test modules (test_policy's
    mode-resolution asserts) see the unpinned default again."""
    from repro.kernels import autotune
    yield
    autotune.set_mode(None)


def _run_and_check(mesh, *, chunk, n_requests=6, slots=3,
                   arch="qwen3-1.7b", align=1, budget=None):
    cfg = get_smoke_config(arch)
    engine = Engine(cfg, mesh, max_batch=slots, max_len=64, chunk=chunk,
                    cache_budget=budget, opts=RunOptions())
    reqs = _requests(n_requests, vocab=cfg.vocab_size, align=align)
    out = engine.run(reqs)
    assert check_lockstep_parity(engine, reqs), \
        "engine tokens diverge from the run-alone lockstep baseline"
    return engine, reqs, out


def test_engine_matches_lockstep_fp32(mesh):
    """Chunked prefill (chunk < prompt lengths) + slot reuse across more
    requests than slots: every request's greedy tokens equal its run-alone
    lockstep tokens, and the telemetry accounts for every admission."""
    engine, reqs, out = _run_and_check(mesh, chunk=8)
    tel = out["telemetry"]
    assert tel["matches"] == len(reqs)      # every request admitted once
    assert tel["evictions"] == len(reqs)    # ... and released once
    assert tel["max_round_matches"] <= engine.scheduler.p - 1
    assert out["completed"] == {r.uid: len(r.out) for r in reqs}
    assert all(len(r.out) == r.max_new for r in reqs)  # no EOS in workload


def test_engine_matches_lockstep_int8_kv(mesh):
    """The int8 KV-cache variant under the pallas attention policy: per-row
    scale composition in the kernel keeps the parity contract.  chunk covers
    the longest prompt so each request calibrates its scales on the same
    (whole-prompt) first chunk the lockstep baseline uses."""
    with policy.apply(impl={"attention": "pallas"},
                      variants={"attention": {"kv_dtype": "int8"}}):
        _run_and_check(mesh, chunk=24, n_requests=4)


def test_engine_matches_lockstep_hybrid(mesh):
    """The hybrid family through the SAME engine loop: LRU/conv state rows
    park under identity updates (a=1, b=0) while neighbours prefill.  chunk
    covers the longest prompt — the LRU h0-fold reassociates across chunk
    boundaries, so single-chunk prefill is the fp-exact arm."""
    _run_and_check(mesh, chunk=24, n_requests=4, arch="recurrentgemma-2b")


def test_engine_matches_lockstep_ssm(mesh):
    """The ssm family through the engine: SSD state is chunk-exact when
    prompt and chunk lengths sit on ``cfg.ssm_chunk`` (= 8) multiples, so
    aligned prompts decode token-identical to the run-alone baseline."""
    _run_and_check(mesh, chunk=16, n_requests=4, arch="mamba2-370m",
                   align=8)


def test_engine_pressure_eviction_requeues_and_finishes(mesh):
    """Eviction under memory pressure: a context budget below the
    workload's working set forces >= 1 eviction; the evicted request
    re-queues through match_round, replays its generated tokens inside the
    re-prefilled prompt, and every request still finishes with its exact
    lockstep tokens."""
    engine, reqs, out = _run_and_check(mesh, chunk=8, budget=40)
    tel = out["telemetry"]
    assert tel["pressure_evictions"] >= 1
    assert tel["matches"] == len(reqs) + tel["pressure_evictions"]
    assert tel["evictions"] == len(reqs)  # completion releases only
    assert all(len(r.out) == r.max_new for r in reqs)


def _assert_snapshot_chains(log):
    """Each request's snapshots chain: its first copies from position 0,
    every later one from the cursor of the one before it."""
    last = {}
    for c in log:
        assert c["since"] == last.get(c["uid"])
        last[c["uid"]] = c["upto"]


def test_engine_pressure_eviction_resumes_from_incremental_snapshots(
        mesh, snapshot_copies):
    """Pressure eviction with a snapshot every 2 tokens: evicted requests
    resume from snapshots built by deltas and go on taking deltas from the
    restored cursor.  Tokens equal the lockstep baseline and the run that
    copies every snapshot's whole row, with as many snapshots; each
    cadence delta moves at most one block of the cadence."""
    cfg = get_smoke_config("qwen3-1.7b")
    runs = {}
    for whole in (False, True):
        engine = Engine(cfg, mesh, max_batch=3, max_len=64, chunk=8,
                        cache_budget=40, snapshot_every=2, opts=RunOptions())
        log = snapshot_copies(engine, whole=whole)
        reqs = _requests(6, vocab=cfg.vocab_size, max_new=12)
        runs[whole] = (engine, reqs, engine.run(reqs)["telemetry"], log)
    engine, reqs, tel, log = runs[False]
    _, whole_reqs, whole_tel, _ = runs[True]
    assert tel["pressure_evictions"] >= 1 and tel["snapshot_restores"] >= 1
    assert check_lockstep_parity(engine, reqs)
    assert [r.out for r in reqs] == [r.out for r in whole_reqs]
    assert tel["snapshots_taken"] == whole_tel["snapshots_taken"] == len(log)
    assert tel["snapshot_bytes"] == sum(c["moved"] for c in log)
    assert tel["snapshot_bytes"] < whole_tel["snapshot_bytes"]
    _assert_snapshot_chains(log)
    deltas = [c for c in log if c["since"] is not None]
    assert deltas and all(c["moved"] <= log.cadence_bound for c in deltas)


def test_engine_batched_prefill_shares_launches(mesh):
    """Batched chunked prefill: with more fresh admissions than one, a
    single padded chunk launch serves >= 2 prefilling slots (chunk-rows
    strictly exceed launches)."""
    _, _, out = _run_and_check(mesh, chunk=8)
    assert out["prefill_chunk_rows"] > out["prefill_chunks"]


def test_engine_unsupported_family_is_structured(mesh, monkeypatch):
    """A model stripped of a serving-contract method fails Engine
    construction with UnsupportedFamilyError carrying the family and the
    missing method name — not an attribute error mid-serve."""
    from repro.models import dense as dense_mod
    monkeypatch.setattr(dense_mod.DenseLM, "prefill_chunk",
                        Model.prefill_chunk)
    cfg = get_smoke_config("qwen3-1.7b")
    with pytest.raises(UnsupportedFamilyError) as ei:
        Engine(cfg, mesh, max_batch=2, max_len=32, chunk=8,
               opts=RunOptions())
    assert ei.value.family == "dense"
    assert ei.value.missing == "prefill_chunk"


def test_engine_decode_compiles_once(mesh):
    """The no-recompile acceptance check: per-row positions are traced
    vectors, so the one decode executable compiled ahead of serving serves
    every ragged composition of slot depths the run produces — and a
    launch at any other shape is refused at once, never recompiled and
    never retried as a fault."""
    cfg = get_smoke_config("qwen3-1.7b")
    engine = Engine(cfg, mesh, max_batch=3, max_len=64, chunk=8,
                    opts=RunOptions())
    engine.compile()
    programs = dict(engine._programs)
    reqs = _requests(6, vocab=cfg.vocab_size)
    out = engine.run(reqs)
    assert out["decode_steps"] > 1
    assert engine._programs == programs
    assert all(engine._programs[k] is p for k, p in programs.items())
    params, tokens, pos, cache, poison = engine._launch_args("decode")
    with pytest.raises(TypeError, match="compiled"):
        engine._launch("decode", "decode", params, tokens[:2], pos[:2],
                       cache, poison[:2])
    assert engine.stats()["faults"]["retries"] == 0


# -- eviction policy + structured stats ---------------------------------------

@pytest.mark.parametrize("evict", ["largest", "coldest"])
def test_engine_evict_policy_replays_exactly(mesh, evict):
    """Both pressure-eviction policies — ``largest`` (most cache rows) and
    ``coldest`` (stalest ``last_step`` stamp) — replay the evicted request
    token-exactly: the victim choice is a scheduling decision, never a
    numerics one."""
    cfg = get_smoke_config("qwen3-1.7b")
    engine = Engine(cfg, mesh, max_batch=3, max_len=64, chunk=8,
                    cache_budget=40, evict_policy=evict, opts=RunOptions())
    reqs = _requests(6, vocab=cfg.vocab_size)
    out = engine.run(reqs)
    assert out["telemetry"]["pressure_evictions"] >= 1
    assert check_lockstep_parity(engine, reqs)
    assert all(len(r.out) == r.max_new for r in reqs)


def test_engine_evict_policy_validated(mesh):
    cfg = get_smoke_config("qwen3-1.7b")
    with pytest.raises(ValueError):
        Engine(cfg, mesh, max_batch=2, max_len=32, chunk=8,
               evict_policy="newest", opts=RunOptions())


def test_engine_stats_structure(mesh):
    """``Engine.stats()`` is the public telemetry surface: consumers (the
    router, benchmarks, these tests) read it instead of private fields.
    The occupancy slices tile max_batch, fault counters carry every PR-9
    key, and the scheduler slice excludes them (no double counting)."""
    from repro.runtime import FAULT_COUNTER_KEYS
    engine, reqs, out = _run_and_check(mesh, chunk=8)
    stats = engine.stats()
    assert stats is not out["stats"]            # fresh dict per call
    occ = stats["occupancy"]
    assert occ["prefilling"] + occ["decoding"] + occ["free"] == \
        engine.max_batch
    assert occ["queued"] == 0 and stats["work_remaining"] == 0  # drained
    assert set(FAULT_COUNTER_KEYS) <= set(stats["faults"])
    assert not set(FAULT_COUNTER_KEYS) & set(stats["scheduler"])
    assert stats["launches"]["decode"] > 0 and stats["busy_s"] > 0
    deg = stats["degradation"]
    assert deg["active_limit"] == deg["max_batch"] == engine.max_batch


# -- SlotScheduler (no model) -------------------------------------------------

def _wr(r):
    return len(r.prompt) + r.max_new - len(r.out)


def test_scheduler_bounded_steals_per_round():
    """Obs. 4.3 at the engine: a round matches at most p - 1 requests of
    the round's priority, even with p idle slots and a deep queue."""
    sched = SlotScheduler(4)
    queue = [Request(i, np.zeros(8, np.int32), max_new=4) for i in range(10)]
    got = sched.assign([0, 1, 2, 3], queue, _wr)
    # all 10 share one priority: rounds of <= 3 until the idle supply drains
    assert len(got) == 4
    assert sched.counters["max_round_matches"] <= 3
    assert sched.counters["rounds"] >= 2


def test_scheduler_priority_order_and_determinism():
    """Largest work-remaining first (the size-based order), idle slots by
    rank; the same inputs always produce the same assignment."""
    sched = SlotScheduler(3)
    queue = [Request(0, np.zeros(4, np.int32), max_new=2),    # work 6
             Request(1, np.zeros(16, np.int32), max_new=8),   # work 24
             Request(2, np.zeros(8, np.int32), max_new=4)]    # work 12
    got = sched.assign([2, 0], queue, _wr)
    assert got == [(0, 1), (2, 2)]  # slot 0 takes the biggest task
    again = SlotScheduler(3).assign([2, 0], queue, _wr)
    assert got == again


def test_scheduler_counters_accumulate():
    sched = SlotScheduler(2)
    queue = [Request(i, np.zeros(4, np.int32), max_new=2) for i in range(3)]
    a = sched.assign([0, 1], queue, _wr)
    assert len(a) == 2 and sched.counters["matches"] == 2
    b = sched.assign([1], queue, _wr)
    assert len(b) == 1 and sched.counters["matches"] == 3
    assert sched.assign([0], [], _wr) == []  # empty queue: no round runs
