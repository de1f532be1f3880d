"""Multi-replica router: placement determinism, fleet fault tolerance.

The acceptance bar for ``repro.launch.router``: whatever the routing arm
(deterministic ``pws`` match rounds or seeded ``rws`` two-choice), the
placements, the in-flight migrations, a replica death mid-decode, and
elastic join/leave, every request's greedy tokens are IDENTICAL,
request-for-request, to a clean single-replica engine run — randomness and
failures perturb *placement*, never tokens.  The ``rws`` two-choice core is
unit-tested without a fleet.
"""
import random
import time

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import rws
from repro.launch.engine import Engine
from repro.launch.mesh import make_debug_mesh
from repro.launch.router import Router
from repro.launch.serve import Request
from repro.models.base import RunOptions
from repro.runtime import FaultInjector

ENGINE_KW = dict(max_batch=2, max_len=64, chunk=8, snapshot_every=2)


@pytest.fixture(scope="module")
def mesh():
    return make_debug_mesh(tp=min(2, len(jax.devices())))


@pytest.fixture(scope="module")
def cfg():
    return get_smoke_config("qwen3-1.7b")


@pytest.fixture(autouse=True)
def _clear_autotune_pin():
    from repro.kernels import autotune
    yield
    autotune.set_mode(None)


def _spec(cfg, n=6, *, seed=0, max_new=6):
    """Skewed workload spec: ragged prompts, mixed generation budgets."""
    rng = np.random.default_rng(seed)
    return [(rng.integers(3, cfg.vocab_size,
                          int(rng.integers(4, 20))).astype(np.int32),
             int(rng.integers(2, max_new + 1)))
            for _ in range(n)]


def _reqs(spec):
    return [Request(i, p, max_new=mn) for i, (p, mn) in enumerate(spec)]


def _kw():
    return dict(ENGINE_KW, opts=RunOptions())


def _assert_single_replica_parity(router, spec, reqs):
    """The oracle: a clean 1-replica engine sharing replica 0's params
    serves the same workload; tokens must match request-for-request."""
    single = Engine(router.cfg, router.mesh, injector=FaultInjector(""),
                    **_kw())
    single.params = router.replicas[0].engine.params
    alone = _reqs(spec)
    single.run(alone)
    assert [r.out for r in alone] == [r.out for r in reqs], \
        "router tokens diverge from the clean single-replica run"


# -- rws two-choice core (no fleet) -------------------------------------------

def test_two_choice_prefers_lighter_lower_id_on_tie():
    # two ids: the two distinct samples always see both; lighter wins
    assert rws.two_choice(random.Random(0), [0, 1], {0: 9, 1: 2}) == 1
    assert rws.two_choice(random.Random(1), [0, 1], {0: 2, 1: 9}) == 0
    # equal loads: lower id breaks the tie
    assert rws.two_choice(random.Random(2), [0, 1], {0: 5, 1: 5}) == 0
    # a single candidate needs no coin
    assert rws.two_choice(random.Random(3), [7], {7: 0}) == 7


def test_two_choice_is_seeded_and_samples_both():
    ids = [0, 1, 2, 3]
    load = {i: i for i in ids}
    picks = [rws.two_choice(random.Random(11), ids, load) for _ in range(8)]
    assert len(set(picks)) == 1                 # same seed, same pick
    rng = random.Random(4)
    seen = {rws.two_choice(rng, ids, load) for _ in range(64)}
    assert len(seen) >= 2                       # the coin really varies
    assert 3 not in seen                        # heaviest never beats a pair


# -- routing arms: determinism + token identity -------------------------------

def test_router_pws_deterministic_balanced_token_identical(mesh, cfg):
    """The deterministic arm: same workload → identical placements run
    after run, both replicas receive work on a skewed workload, the
    match-round invariants (asserted inside ``_route_pws``) hold, and the
    tokens equal the clean single-replica oracle."""
    spec = _spec(cfg)
    router = Router(cfg, mesh, n_replicas=2, route="pws", **_kw())
    a = _reqs(spec)
    out1 = router.run(a)
    b = _reqs(spec)
    out2 = router.run(b)
    assert out1["placements"] == out2["placements"]
    assert [r.out for r in a] == [r.out for r in b]
    routed = out1["counters"]["routed"]
    assert routed[0] > 0 and routed[1] > 0
    assert {u for u, _ in out1["placements"]} == {r.uid for r in a}
    assert out1["counters"]["route_rounds"] > 0
    _assert_single_replica_parity(router, spec, a)


def test_router_rws_seeded_balanced_token_identical(mesh, cfg):
    """The randomized arm: the seed fixes the placement sequence (re-seeded
    per ``begin``), two-choice spreads a skewed workload over both
    replicas, and tokens still equal the deterministic oracle — randomness
    perturbs placement only."""
    spec = _spec(cfg)
    router = Router(cfg, mesh, n_replicas=2, route="rws", seed=5, **_kw())
    a = _reqs(spec)
    out1 = router.run(a)
    b = _reqs(spec)
    out2 = router.run(b)
    assert out1["placements"] == out2["placements"]
    routed = out1["counters"]["routed"]
    assert routed[0] > 0 and routed[1] > 0
    _assert_single_replica_parity(router, spec, a)


# -- replica death → checkpoint-streamed respawn ------------------------------

def test_router_replica_death_respawns_token_identical(mesh, cfg):
    """Failure-model tier (d): replica 1's decode launches fail through the
    retry budget, the escalated ``LaunchFailedError`` marks it dead, its
    in-flight requests re-queue router-wide with their host snapshots, and
    a replacement streams up from the fleet checkpoint — with every token
    identical to a clean single-replica run."""
    spec = _spec(cfg, n=6, max_new=8)
    router = Router(cfg, mesh, n_replicas=2, route="pws",
                    fleet_faults="|decode@3=raise:99", **_kw())
    reqs = _reqs(spec)
    out = router.run(reqs)
    c = out["counters"]
    assert c["replica_deaths"] == 1
    assert c["replica_restarts"] >= 1
    assert c["requeued_on_death"] >= 1
    assert c["migrations"] >= 1        # >= 1 cross-replica snapshot resume
    assert router.replicas[1].state == "dead"
    assert any(r.rid >= 2 and r.spawned_from == "checkpoint"
               and r.state == "live" for r in router.replicas)
    assert all(len(r.out) == r.max_new for r in reqs)
    _assert_single_replica_parity(router, spec, reqs)


def test_router_stops_on_program_error(mesh, cfg, monkeypatch):
    """A program error on a replica is not a death: the router respawns
    nothing and the error propagates out of ``run`` at once."""
    router = Router(cfg, mesh, n_replicas=2, route="pws", **_kw())

    def broken(*args, **kwargs):
        raise ValueError("broken decode program")

    for rep in router.replicas:
        monkeypatch.setattr(rep.engine.model, "decode_step", broken)
    t0 = time.monotonic()
    with pytest.raises(ValueError, match="broken decode program"):
        router.run(_reqs(_spec(cfg, n=4)))
    assert time.monotonic() - t0 < 120
    assert router.counters["replica_deaths"] == 0
    assert router.counters["replica_restarts"] == 0
    assert len(router.replicas) == 2


def test_router_restart_budget_stops_the_run(mesh, cfg):
    """A death past the ``max_restarts`` budget stops the run with an
    error instead of respawning forever."""
    router = Router(cfg, mesh, n_replicas=2, route="pws",
                    fleet_faults="|decode@0=raise:99", **_kw())
    router.max_restarts = 0
    with pytest.raises(RuntimeError, match="restart budget"):
        router.run(_reqs(_spec(cfg, n=4)))
    assert router.counters["replica_deaths"] == 1
    assert router.counters["replica_restarts"] == 0


# -- in-flight rebalancing ----------------------------------------------------

def test_router_rebalance_migrates_decode_slot_exactly(mesh, cfg):
    """Queue-depth skew rebalancing: one long request next to shorts leaves
    the fleet skewed once the shorts drain; the router drains the donor's
    decoding slot and the recipient resumes it from the host snapshot —
    slot migration is token-exact and the recipient really restores (its
    ``snapshot_restores`` counter moves)."""
    spec = [(np.arange(3, 15, dtype=np.int32), 20),
            (np.arange(3, 11, dtype=np.int32), 2),
            (np.arange(4, 12, dtype=np.int32), 2),
            (np.arange(5, 13, dtype=np.int32), 2)]
    router = Router(cfg, mesh, n_replicas=2, route="pws",
                    rebalance_threshold=4, queue_depth=0, **_kw())
    reqs = _reqs(spec)
    out = router.run(reqs)
    c = out["counters"]
    assert c["rebalances"] >= 1
    assert c["slot_migrations"] >= 1
    assert c["migrations"] >= 1
    restores = sum(row["faults"]["snapshot_restores"]
                   for row in out["replicas"])
    assert restores >= 1
    _assert_single_replica_parity(router, spec, reqs)


def test_drain_migration_takes_further_deltas_on_recipient(
        mesh, cfg, snapshot_copies):
    """A decoding row drained from one replica after a first snapshot and
    a delta (``drain_slot`` stages one more delta) resumes on another
    replica, whose snapshots of it are deltas from the adopted cursor.
    Tokens equal a clean single-replica run and the run that copies every
    snapshot's whole row, with as many snapshots on each replica."""
    spec = [(np.arange(3, 14, dtype=np.int32), 14)]
    runs = {}
    for whole in (False, True):
        donor, rec = (Engine(cfg, mesh, injector=FaultInjector(""), **_kw())
                      for _ in range(2))
        rec.params = donor.params
        logs = [snapshot_copies(e, whole=whole) for e in (donor, rec)]
        req, = _reqs(spec)
        donor.begin([req])
        while len(req.out) < 5:   # snapshots at 2 and 4 tokens
            donor.step()
        req, snap = donor.drain_slot(0)
        rec.begin([])
        rec.adopt(req, snap)
        while rec.busy():
            rec.step()
        runs[whole] = (req, [e.finish()["faults"] for e in (donor, rec)],
                       logs, snap["pos"])
    single = Engine(cfg, mesh, injector=FaultInjector(""), **_kw())
    single.params = donor.params
    alone, = _reqs(spec)
    single.run([alone])
    req, faults, (dlog, rlog), adopted = runs[False]
    whole_req, whole_faults, _, _ = runs[True]
    assert req.out == whole_req.out == alone.out
    assert len(req.out) == spec[0][1]
    assert [f["snapshots_taken"] for f in faults] \
        == [f["snapshots_taken"] for f in whole_faults] \
        == [len(dlog), len(rlog)]
    prompt = len(spec[0][0])
    assert [(c["since"], c["upto"]) for c in dlog] == [
        (None, prompt + 1), (prompt + 1, prompt + 3), (prompt + 3, adopted)]
    assert rlog and rlog[0]["since"] == adopted
    assert all(b["since"] == a["upto"] for a, b in zip(rlog, rlog[1:]))
    assert all(c["moved"] <= rlog.cadence_bound for c in rlog)
    assert faults[1]["snapshot_restores"] == 1


# -- elastic join / leave -----------------------------------------------------

def test_router_elastic_join_and_leave_token_identical(mesh, cfg):
    """Live re-mesh: a replica joins mid-run (checkpoint-streamed, starts
    taking placements), another leaves (its queue and in-flight decodes
    drain back through the snapshot path) — the fleet finishes every
    request token-identically."""
    spec = _spec(cfg, n=10, seed=2, max_new=8)
    router = Router(cfg, mesh, n_replicas=2, route="pws", **_kw())
    reqs = _reqs(spec)
    router.begin(reqs)
    for _ in range(2):
        router.step_round()
    joiner = router.add_replica()
    assert joiner.spawned_from == "checkpoint"
    for _ in range(2):
        router.step_round()
    router.remove_replica(1)
    while not router.done():
        router.step_round()
    out = router.finish(reqs)
    c = out["counters"]
    assert c["joins"] == 1 and c["leaves"] == 1
    assert c["routed"].get(joiner.rid, 0) >= 1
    states = {r.rid: r.state for r in router.replicas}
    assert states[1] == "left" and states[joiner.rid] == "live"
    assert all(len(r.out) == r.max_new for r in reqs)
    _assert_single_replica_parity(router, spec, reqs)


def test_router_remove_guards(mesh, cfg):
    router = Router(cfg, mesh, n_replicas=2, route="pws", **_kw())
    router.remove_replica(1)
    with pytest.raises(ValueError, match="not live"):
        router.remove_replica(1)
    with pytest.raises(ValueError, match="last live"):
        router.remove_replica(0)


# -- health-score load shedding -----------------------------------------------

def test_router_health_shedding_routes_around_faulty_replica(mesh, cfg):
    """A replica whose launches keep failing folds its PR-9 retry counters
    into a health score under the shed threshold; the router stops placing
    new work there (sheds counted) while the healthy replica finishes the
    queue — tokens still exact."""
    spec = _spec(cfg, n=10, seed=3, max_new=6)
    plan = "|decode@1=raise,decode@2=raise"
    router = Router(cfg, mesh, n_replicas=2, route="pws",
                    fleet_faults=plan, degrade_after=2, degrade_window=16,
                    heal_after=64, **_kw())
    reqs = _reqs(spec)
    out = router.run(reqs)
    sick = router.replicas[1]
    assert sick.state == "live"                  # retries recovered, no death
    assert sick.health < 0.5 and sick.shed()
    assert out["counters"]["sheds"] >= 1
    assert out["counters"]["replica_deaths"] == 0
    _assert_single_replica_parity(router, spec, reqs)


# -- provenance rows ----------------------------------------------------------

def test_router_provenance_rows(mesh, cfg):
    """Every replica contributes a provenance row: identity, how it was
    born, its mesh, the kernel policy description and autotune table
    provenance, and the live health/fault picture."""
    spec = _spec(cfg, n=4)
    router = Router(cfg, mesh, n_replicas=2, route="pws", **_kw())
    out = router.run(_reqs(spec))
    rows = out["replicas"]
    assert [row["rid"] for row in rows] == [0, 1]
    assert [row["spawned_from"] for row in rows] == ["init", "checkpoint"]
    for row in rows:
        assert row["state"] == "live"
        assert row["mesh"] == dict(mesh.shape)
        assert isinstance(row["policy"], str) and row["policy"]
        assert "mode" in row["autotune"]
        assert 0.0 <= row["health"] <= 1.0
        assert "retries" in row["faults"]
