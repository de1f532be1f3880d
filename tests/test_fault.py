"""Fault-tolerant serving: deterministic injection, bounded retry,
slot-snapshot recovery, graceful degradation, elastic restart.

The acceptance bar: under a seeded fault plan (decode raises, prefill
delays, poisoned slots) the engine's greedy tokens are IDENTICAL,
request-for-request, to the clean run — recovery must be invisible to
numerics — with ``snapshot_restores >= 1`` confirming the snapshot path
(not whole-residency replay) carried the recovery.  The injector and
retry policy are unit-tested without a model; the engine tests reuse one
module-scoped engine and drive different plans through it.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import save_checkpoint
from repro.configs import get_smoke_config
from repro.launch.engine import Engine, check_lockstep_parity
from repro.launch.mesh import make_debug_mesh
from repro.launch.serve import Request
from repro.models.base import RunOptions
from repro.runtime.fault_tolerance import (
    FaultInjector,
    FaultPolicy,
    InjectedFault,
    LaunchFailedError,
    StragglerMonitor,
    parse_fault_plan,
)


@pytest.fixture(autouse=True)
def _clear_autotune_pin():
    """Server.__init__ pins the autotune mode process-wide; clear it so
    later test modules see the unpinned default again."""
    from repro.kernels import autotune
    yield
    autotune.set_mode(None)


# -- plan grammar + injector (no model) --------------------------------------

def test_fault_plan_parsing():
    specs = parse_fault_plan(
        "decode@12=raise,prefill@3=delay:0.2,slot@2=nan_logits:4")
    assert [(s.kind, s.index, s.action) for s in specs] == [
        ("decode", 12, "raise"), ("prefill", 3, "delay"),
        ("slot", 2, "nan_logits")]
    assert specs[1].arg == pytest.approx(0.2)
    assert specs[2].remaining == 4
    assert parse_fault_plan("") == []
    assert parse_fault_plan("decode@0=raise:3")[0].remaining == 3


@pytest.mark.parametrize("bad", [
    "decode@12",                 # no action
    "decode=raise",              # no index
    "warp@1=raise",              # unknown kind
    "decode@1=explode",          # unknown action
    "decode@1=nan_logits",       # nan_logits targets a slot
    "slot@1=raise",              # raise targets a launch
    "decode@x=raise",            # non-integer index
])
def test_fault_plan_rejects_malformed(bad):
    with pytest.raises(ValueError):
        parse_fault_plan(bad)


def test_injector_from_env(monkeypatch):
    monkeypatch.setenv("REPRO_FAULTS", "decode@5=raise")
    inj = FaultInjector.from_env()
    assert bool(inj) and inj.describe() == "decode@5=raise"
    monkeypatch.delenv("REPRO_FAULTS")
    assert not FaultInjector.from_env()


def test_injector_deterministic_fire_sequence():
    """The same plan fires at the same launches every time: a raise burns
    one count per attempt (so the bounded retry of that launch succeeds),
    and a slot poison counts eligible decode launches down to its n-th."""
    def drive(inj):
        events = []
        for ordinal in range(6):
            try:
                inj.before_launch("decode", ordinal)
            except InjectedFault:
                events.append(("raise", ordinal))
                inj.before_launch("decode", ordinal)  # retry passes
            events.append(("poison", ordinal,
                           tuple(inj.poison_rows([0, 1]))))
        return events

    plan = "decode@2=raise,slot@1=nan_logits:3"
    a, b = drive(FaultInjector(plan)), drive(FaultInjector(plan))
    assert a == b
    assert ("raise", 2) in a
    # the slot poison fires on the 3rd decode launch in which slot 1 decodes
    assert ("poison", 2, (1,)) in a
    assert sum(1 for e in a if e[0] == "poison" and e[2]) == 1


def test_fault_policy_backoff_seeded():
    pol = FaultPolicy(backoff_s=0.01, backoff_mult=2.0, jitter=0.5, seed=7)
    a = [pol.backoff(i, pol.make_rng()) for i in range(3)]
    b = [pol.backoff(i, pol.make_rng()) for i in range(3)]
    assert a == b                              # seeded: reproducible
    for i, d in enumerate(a):                  # jitter bounded above base
        base = 0.01 * 2.0 ** i
        assert base <= d <= base * 1.5


# -- engine fault paths -------------------------------------------------------

def _requests(n, vocab, *, seed=0, max_new=8):
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(3, vocab,
                                    int(rng.integers(4, 20))).astype(np.int32),
                    max_new=int(rng.integers(2, max_new + 1)))
            for i in range(n)]


@pytest.fixture(scope="module")
def mesh():
    return make_debug_mesh(tp=min(2, len(jax.devices())))


@pytest.fixture(scope="module")
def served(mesh):
    """One engine + its clean-run baseline, shared by the fault tests:
    every faulted run must reproduce ``clean_outs`` exactly."""
    cfg = get_smoke_config("qwen3-1.7b")
    engine = Engine(cfg, mesh, max_batch=3, max_len=64, chunk=8,
                    snapshot_every=2, injector=FaultInjector(""),
                    heal_after=4, opts=RunOptions())
    spec = [(r.prompt, r.max_new) for r in _requests(5, cfg.vocab_size)]
    reqs = [Request(i, p, max_new=mn) for i, (p, mn) in enumerate(spec)]
    engine.run(reqs)
    return engine, spec, [list(r.out) for r in reqs]


def _faulted_run(served_fixture, plan, **knobs):
    engine, spec, clean_outs = served_fixture
    engine.injector = FaultInjector(plan)
    for k, v in knobs.items():
        setattr(engine, k, v)
    reqs = [Request(i, p, max_new=mn) for i, (p, mn) in enumerate(spec)]
    out = engine.run(reqs)
    return engine, reqs, out, clean_outs


def test_engine_decode_raise_retries_token_identical(served):
    """An injected decode-launch failure retries under the bounded backoff
    and the run's tokens are request-for-request the clean run's."""
    engine, reqs, out, clean = _faulted_run(served, "decode@1=raise")
    tel = out["telemetry"]
    assert tel["retries"] >= 1 and tel["faults_injected"] == 1
    assert [r.out for r in reqs] == clean
    assert check_lockstep_parity(engine, reqs)


def test_engine_prefill_delay_rides_through(served):
    """An injected prefill straggler slows the launch but changes nothing
    else — no retry, no eviction, identical tokens."""
    engine, reqs, out, clean = _faulted_run(served, "prefill@1=delay:0.05")
    tel = out["telemetry"]
    assert tel["faults_injected"] == 1
    assert tel["retries"] == 0 and tel["slots_poisoned"] == 0
    assert [r.out for r in reqs] == clean


def test_engine_watchdog_flags_injected_straggler(served):
    """The per-launch watchdog: a late injected delay lands far outside
    the rolling wall-time window (fresh monitor, compile times excluded)
    and is flagged; tokens are untouched."""
    served[0].watchdog = StragglerMonitor(window=32, k_sigma=4.0,
                                          min_samples=5)
    engine, reqs, out, clean = _faulted_run(served, "decode@5=delay:0.5")
    assert out["telemetry"]["stragglers"] >= 1
    assert [r.out for r in reqs] == clean


def test_engine_launch_exhaustion_raises(served):
    """Failure model (a): a launch that fails every bounded attempt
    escalates as LaunchFailedError for job-level restart."""
    engine, spec, _ = served
    engine.injector = FaultInjector("decode@0=raise:99")
    old = engine.fault_policy
    engine.fault_policy = FaultPolicy(max_retries=1, backoff_s=1e-4)
    try:
        with pytest.raises(LaunchFailedError) as ei:
            engine.run([Request(0, spec[0][0], max_new=4)])
        assert ei.value.kind == "decode" and ei.value.attempts == 2
    finally:
        engine.fault_policy = old


def test_engine_program_error_raises_at_once(mesh, monkeypatch):
    """A program error — here a ValueError raised while the decode step is
    traced — is not a fault: it propagates on the first attempt, with no
    retry and no LaunchFailedError."""
    cfg = get_smoke_config("qwen3-1.7b")
    engine = Engine(cfg, mesh, max_batch=2, max_len=32, chunk=8,
                    injector=FaultInjector(""), opts=RunOptions())
    calls = []

    def broken(*args, **kwargs):
        calls.append(1)
        raise ValueError("broken decode program")

    monkeypatch.setattr(engine.model, "decode_step", broken)
    with pytest.raises(ValueError, match="broken decode program"):
        engine.run([Request(0, np.arange(3, 9, dtype=np.int32), max_new=4)])
    assert len(calls) == 1
    assert engine.scheduler.counters["retries"] == 0


def test_engine_poisoned_slot_bisected_and_restored(served):
    """Failure model (b): one slot's logits go non-finite; the per-row
    validity vector bisects it, ONLY that request is re-queued, it resumes
    from its last snapshot, and every request's tokens match the clean
    run."""
    engine, reqs, out, clean = _faulted_run(served, "slot@1=nan_logits:3")
    tel = out["telemetry"]
    assert tel["slots_poisoned"] == 1
    assert tel["snapshot_restores"] >= 1       # snapshot, not full replay
    assert tel["matches"] == len(reqs) + 1     # exactly one re-admission
    assert tel["evictions"] == len(reqs)       # completion releases only
    assert [r.out for r in reqs] == clean
    assert check_lockstep_parity(engine, reqs)


def test_engine_poisoned_row_resumes_from_incremental_snapshot(
        mesh, snapshot_copies):
    """A row poisoned after a first snapshot and two deltas resumes from
    the snapshot the deltas built: its tokens are the lockstep baseline's
    and the whole-row-copy run's, with as many snapshots, and its next
    snapshot is a delta from the restored cursor."""
    cfg = get_smoke_config("qwen3-1.7b")
    rng = np.random.default_rng(3)
    spec = [(rng.integers(3, cfg.vocab_size, 9).astype(np.int32), 12)
            for _ in range(2)]
    runs = {}
    for whole in (False, True):
        # slot 0 snapshots at 2, 4 and 6 tokens, then poisons at its 7th
        # decode launch
        engine = Engine(cfg, mesh, max_batch=2, max_len=64, chunk=8,
                        snapshot_every=2,
                        injector=FaultInjector("slot@0=nan_logits:7"),
                        opts=RunOptions())
        log = snapshot_copies(engine, whole=whole)
        reqs = [Request(i, p, max_new=mn) for i, (p, mn) in enumerate(spec)]
        runs[whole] = (engine, reqs, engine.run(reqs)["telemetry"], log)
    engine, reqs, tel, log = runs[False]
    _, whole_reqs, whole_tel, _ = runs[True]
    assert tel["slots_poisoned"] == 1 and tel["snapshot_restores"] == 1
    assert check_lockstep_parity(engine, reqs)
    assert [r.out for r in reqs] == [r.out for r in whole_reqs]
    assert tel["snapshots_taken"] == whole_tel["snapshots_taken"] == len(log)
    assert tel["snapshot_bytes"] < whole_tel["snapshot_bytes"]
    row0 = [(c["since"], c["upto"]) for c in log if c["uid"] == 0]
    prompt = len(spec[0][0])
    # 2, 4, 6 tokens out; poisoned before the 8th; resumed at 6 tokens
    assert row0[:4] == [(None, prompt + 1), (prompt + 1, prompt + 3),
                        (prompt + 3, prompt + 5), (prompt + 5, prompt + 7)]
    deltas = [c for c in log if c["since"] is not None]
    assert all(c["moved"] <= log.cadence_bound for c in deltas)


def test_engine_degradation_shrinks_and_heals(mesh):
    """Failure model (c): repeated faults inside the window shrink the
    active-slot limit; sustained health probes it back up — and the
    scheduling change never touches tokens."""
    cfg = get_smoke_config("qwen3-1.7b")
    engine = Engine(cfg, mesh, max_batch=3, max_len=64, chunk=8,
                    injector=FaultInjector("decode@2=raise,decode@3=raise"),
                    degrade_after=2, degrade_window=8, heal_after=4,
                    opts=RunOptions())
    rng = np.random.default_rng(1)
    reqs = [Request(i, rng.integers(3, cfg.vocab_size, 8).astype(np.int32),
                    max_new=14) for i in range(3)]
    out = engine.run(reqs)
    tel = out["telemetry"]
    assert tel["degradations"] >= 1
    assert tel["degraded_iters"] >= 1
    deg = engine.stats()["degradation"]
    assert deg["active_limit"] == deg["max_batch"]   # healed by run end
    assert not deg["degraded"]
    assert check_lockstep_parity(engine, reqs)


def test_engine_fault_storm_acceptance(served):
    """The acceptance criterion: >= 1 decode raise + >= 1 prefill delay +
    >= 1 poisoned slot in one seeded plan; greedy tokens request-for-request
    identical to the clean run with snapshot_restores >= 1."""
    engine, reqs, out, clean = _faulted_run(
        served, "decode@1=raise,prefill@1=delay:0.05,slot@0=nan_logits:4")
    tel = out["telemetry"]
    assert tel["faults_injected"] == 3
    assert tel["retries"] >= 1
    assert tel["slots_poisoned"] == 1
    assert tel["snapshot_restores"] >= 1
    assert [r.out for r in reqs] == clean
    assert check_lockstep_parity(engine, reqs)


# -- elastic restart ----------------------------------------------------------

def test_engine_elastic_restart_identical_logits(mesh, tmp_path):
    """Serving restart on a re-planned (shrunken when devices allow) mesh:
    params restore through elastic.serving_restore and the restarted
    replica's logits — and greedy tokens — are identical to the source
    replica's.  Params are perturbed before saving so the assertion cannot
    pass on a fresh init."""
    cfg = get_smoke_config("qwen3-1.7b")
    src = Engine(cfg, mesh, max_batch=2, max_len=64, chunk=8,
                 opts=RunOptions())
    src.params = jax.tree.map(lambda x: x * 1.5, src.params)
    save_checkpoint(tmp_path, 3, {"params": src.params},
                    mesh_shape=dict(mesh.shape))

    small = make_debug_mesh(1, tp=1)  # a strict shrink when >1 device
    restarted = Engine.restart(cfg, small, tmp_path, max_batch=2,
                               max_len=64, chunk=8, opts=RunOptions())

    prompt = np.arange(3, 11, dtype=np.int32)
    batch = {"tokens": jnp.asarray(prompt)[None]}
    from repro.core.sharding_hints import axis_rules
    with mesh, axis_rules(src.rules, mesh):
        la, _ = src._prefill(src.params, batch)
    with small, axis_rules(restarted.rules, small):
        lb, _ = restarted._prefill(restarted.params, batch)
    np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))

    a = [Request(0, prompt, max_new=6)]
    b = [Request(0, prompt, max_new=6)]
    src.run(a)
    restarted.run(b)
    assert a[0].out == b[0].out
