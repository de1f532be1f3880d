"""Host spans and request marks: the span aggregate's arithmetic, the
engine's spans against its own counters, the marks every served request
carries, and the names of the engine's compiled programs."""
import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.launch.engine import Engine
from repro.launch.mesh import make_debug_mesh
from repro.launch.serve import Request
from repro.models.base import RunOptions
from repro.runtime.spans import Spans


@pytest.fixture(autouse=True)
def _clear_autotune_pin():
    from repro.kernels import autotune
    yield
    autotune.set_mode(None)


def test_self_seconds_leave_out_child_spans():
    spans = Spans()
    with spans.span("outer") as outer:
        with spans.span("inner") as a:
            pass
        with spans.span("inner") as b:
            pass
    agg = spans.snapshot()
    assert agg["inner"]["n"] == 2 and agg["outer"]["n"] == 1
    assert agg["inner"]["s"] == pytest.approx(a.s + b.s)
    assert agg["outer"]["s"] == outer.s == spans.total("outer")
    assert agg["outer"]["self_s"] == pytest.approx(outer.s - a.s - b.s)
    assert agg["inner"]["self_s"] == agg["inner"]["s"]
    assert spans.total("never") == 0.0
    spans.reset()
    assert spans.snapshot() == {}


def test_span_closes_on_an_exception():
    spans = Spans()
    with pytest.raises(ValueError):
        with spans.span("outer"):
            with spans.span("inner"):
                raise ValueError
    assert {k: v["n"] for k, v in spans.snapshot().items()} == {
        "outer": 1, "inner": 1}
    with spans.span("next"):  # the stack is empty again: no parent
        pass
    assert spans.snapshot()["outer"]["n"] == 1


@pytest.fixture(scope="module")
def served():
    """A small run with chunked prefill, slot reuse and snapshots."""
    cfg = get_smoke_config("qwen3-1.7b")
    engine = Engine(cfg, make_debug_mesh(tp=min(2, len(jax.devices()))),
                    max_batch=3, max_len=64, chunk=8, snapshot_every=3,
                    opts=RunOptions())
    compile_s = engine.compile()
    compile_spans = engine.stats()["spans"]["engine.compile"]
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(3, cfg.vocab_size, int(rng.integers(
        4, 20))).astype(np.int32), max_new=int(rng.integers(4, 10)))
        for i in range(6)]
    out = engine.run(reqs)
    return engine, reqs, out, (compile_s, compile_spans)


def test_engine_spans_agree_with_its_counters(served):
    engine, _, out, _ = served
    stats = engine.stats()
    spans = stats["spans"]
    assert spans["engine.step"]["n"] == stats["degradation"]["iter"]
    assert spans["engine.step"]["s"] == stats["busy_s"] == out["busy_s"]
    launches = stats["launches"]["prefill"] + stats["launches"]["decode"]
    assert spans["engine.wait"]["n"] == spans["engine.launch"]["n"] \
        == launches
    assert spans["engine.prefill"]["n"] == stats["launches"]["prefill"]
    assert spans["engine.decode"]["n"] == stats["launches"]["decode"]
    assert spans["engine.snapshot"]["n"] \
        == stats["faults"]["snapshots_taken"] > 0
    assert spans["engine.admit"]["n"] >= 1
    # compiled ahead of the run, and begin() starts a fresh aggregate
    assert "engine.compile" not in spans


def test_engine_spans_nest_inside_their_parents(served):
    spans = served[0].stats()["spans"]
    s = {k: v["s"] for k, v in spans.items()}
    for v in spans.values():
        assert 0 <= v["self_s"] <= v["s"] + 1e-12
    eps = 1e-9
    assert s["engine.admit"] + s["engine.prefill"] + s["engine.decode"] \
        <= s["engine.step"] + eps
    assert s["engine.launch"] + s["engine.wait"] + s["engine.snapshot"] \
        <= s["engine.prefill"] + s["engine.decode"] + eps


def test_compile_seconds_come_from_compile_spans(served):
    compile_s, spans = served[3]
    assert set(compile_s) == {"decode", "prefill_first", "prefill_cont"}
    assert all(v > 0 for v in compile_s.values())
    assert spans["n"] == 3
    assert spans["s"] == pytest.approx(sum(compile_s.values()))


def test_served_requests_carry_ordered_marks(served):
    _, reqs, _, _ = served
    for r in reqs:
        assert r.queued <= r.admitted <= r.first_token


def test_marks_survive_readmission():
    """A request re-queued by pressure eviction keeps its first marks."""
    cfg = get_smoke_config("qwen3-1.7b")
    engine = Engine(cfg, make_debug_mesh(tp=1), max_batch=3, max_len=64,
                    chunk=8, cache_budget=40, opts=RunOptions())
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(3, cfg.vocab_size, 16).astype(np.int32),
                    max_new=8) for i in range(4)]
    engine.begin(reqs)
    first = {}
    while engine.busy():
        engine.step()
        for r in reqs:
            for mark in ("queued", "admitted", "first_token"):
                if getattr(r, mark) is not None:
                    first.setdefault((r.uid, mark), getattr(r, mark))
    sched = engine.finish()["scheduler"]
    assert sched["pressure_evictions"] >= 1
    assert sched["matches"] > len(reqs)  # some request was admitted twice
    assert {(r.uid, mark): getattr(r, mark) for r in reqs
            for mark in ("queued", "admitted", "first_token")} == first


@pytest.mark.parametrize("launch,module", [
    ("decode", "jit_decode_rows"),
    ("prefill_first", "jit_prefill_first"),
    ("prefill_cont", "jit_prefill_cont"),
])
def test_launches_lower_as_named_modules(served, launch, module):
    engine = served[0]
    assert engine._programs[launch].as_text().startswith(
        f"HloModule {module},")
