"""The per-layer metrics that read the engine's host spans, its request
marks and its named prefill programs."""
import time
from types import SimpleNamespace

import jax
import pytest

import benchroot
import run
import serving
import spec
from devtrace import Event, Trace

SPAN_METRICS = ("engine.host_share", "engine.snapshot_share",
                "engine.queue_wait_p90_ms", "engine.prefill_p90_ms")


@pytest.fixture(scope="module")
def traced_chat(tmp_path_factory):
    root, bench_spec = benchroot.build(tmp_path_factory.mktemp("bench"))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(run, "require_accelerator",
                   lambda chips: jax.devices()[:chips])
        return run.run_cell("tiny.chat", 2**31 + 7, 4.0, True, root=root,
                            spec=bench_spec, t_start=time.perf_counter())


def test_traced_chat_reports_the_span_metrics(traced_chat):
    out = traced_chat
    assert out["correct"], out["compared"]
    got = {m: out["metrics"][m]["value"] for m in SPAN_METRICS}
    assert 0 < got["engine.host_share"] <= 100
    assert 0 < got["engine.snapshot_share"] <= got["engine.host_share"]
    assert got["engine.queue_wait_p90_ms"] >= 0
    assert got["engine.prefill_p90_ms"] > 0
    assert {out["metrics"][m]["unit"] for m in SPAN_METRICS} == {"%", "ms"}


def _served(stats_w0, stats_w1, records=(), drain_end=40.0):
    return serving.Served(list(records), 10.0, 20.0, 20.0, drain_end, [],
                          stats_w0, stats_w1, [])


def _spans(wait, snapshot):
    return {"spans": {"engine.wait": {"n": 1, "s": wait, "self_s": wait},
                      "engine.snapshot": {"n": 1, "s": snapshot,
                                          "self_s": snapshot}}}


def test_span_shares_take_the_window_delta():
    s = _served(_spans(3.0, 1.0), _spans(9.0, 2.5))
    r = SimpleNamespace(served=s)
    # 6 s of 10 waiting on launches, 1.5 s of 10 copying rows
    assert spec.metric_reader("engine.host_share")(r) == pytest.approx(40.0)
    assert spec.metric_reader("engine.snapshot_share")(r) == \
        pytest.approx(15.0)
    # no snapshot closed yet: a share of 0, not a missing reading
    r = SimpleNamespace(served=_served({"spans": {}}, {"spans": {}}))
    assert spec.metric_reader("engine.snapshot_share")(r) == 0.0
    assert spec.metric_reader("engine.host_share")(r) == 100.0


def _rec(queued, admitted, first_token):
    req = SimpleNamespace(queued=queued, admitted=admitted,
                          first_token=first_token)
    return serving.Record(0, 8, 4, queued, queued, req)


def test_marks_split_queue_wait_from_prefill():
    recs = [_rec(10.0 + i, 10.0 + 1.1 * i, 11.0 + 2 * i) for i in range(9)]
    recs.append(_rec(19.0, None, None))     # never admitted by drain end
    s = _served({}, {}, recs)
    r = SimpleNamespace(served=s, tracked=recs)
    waits = [0.1 * i for i in range(9)] + [40.0 - 19.0]
    assert spec.metric_reader("engine.queue_wait_p90_ms")(r) == \
        pytest.approx(serving.percentile(waits, 90) * 1e3)
    prefill = [1.0 + 0.9 * i for i in range(9)]
    assert spec.metric_reader("engine.prefill_p90_ms")(r) == \
        pytest.approx(serving.percentile(prefill, 90) * 1e3)


def test_engine_without_spans_or_marks_reads_nothing():
    """An engine that predates the spans and marks: every reader returns
    None, and none raises."""
    recs = [serving.Record(0, 8, 4, 10.0, 10.0, SimpleNamespace(out=[]),
                           [11.0], 12.0)]
    r = SimpleNamespace(served=_served({"launches": {}}, {"launches": {}},
                                       recs), tracked=recs)
    assert all(spec.metric_reader(m)(r) is None for m in SPAN_METRICS)


def test_prefill_program_time_is_the_mean_of_both_programs():
    modules = {"TPU:0": [Event("jit_prefill_first(7)", 0, 4e6),
                         Event("jit_decode_rows(8)", 5e6, 1e6),
                         Event("jit_prefill_cont(9)", 7e6, 2e6),
                         Event("jit_prefill_cont(9)", 10e6, 3e6)]}
    read = spec.metric_reader("step.prefill_ms")
    assert read(SimpleNamespace(trace=Trace(0, 20e6, {}, modules))) == \
        pytest.approx(3.0)
    # the unnamed programs of an engine that jits partials are not found
    unnamed = {"TPU:0": [Event("jit__unknown(3)", 0, 4e6)]}
    assert read(SimpleNamespace(trace=Trace(0, 20e6, {}, unnamed))) is None
