"""The reduction from a profiler trace to busy time, idle share, kernel
time and the breakdown."""
from pathlib import Path

import pytest

import devtrace
from devtrace import Event, Trace

DATA = Path(__file__).resolve().parent / "data"


def _synthetic():
    ops = {"TPU:0": [Event("a", 0, 10),
                     Event("b", 5, 10),   # overlaps a
                     Event("c", 30, 5),
                     Event("a", 50, 20),   # runs past end
                     Event("d", -10, 15)]}  # starts before
    modules = {"TPU:0": [Event("jit_decode_rows(1)", 0, 15),
                         Event("jit_chunk_step(2)", 30, 40)]}
    spans = [Event("bench.step", 0, 40), Event("bench.generator", 40, 5),
             Event("bench.step", 45, 15)]
    return Trace(0, 60, ops, modules, spans)


def test_union_of_overlapping_intervals():
    assert devtrace.union_ns([(0, 10), (5, 15), (30, 35), (20, 20)]) == 20
    assert devtrace.union_ns([]) == 0


def test_busy_and_idle_are_clipped_to_the_window():
    tr = _synthetic()
    # busy: [0, 15) from d, a, b; [30, 35); [50, 60) clipped
    assert devtrace.busy_s(tr) == pytest.approx(30e-9)
    assert devtrace.idle_gaps(tr) == [(15, 30), (35, 50)]
    assert tr.window_s == pytest.approx(60e-9)


def test_breakdown_sums_ops_and_names_gaps_by_host_span():
    b = devtrace.breakdown(_synthetic())
    assert b["device_ops"][0] == ["a", pytest.approx(20e-9)]
    assert b["idle_gaps"] == [["bench.step", pytest.approx(15e-9)],
                              ["bench.generator", pytest.approx(15e-9)]]


MATMUL = ("%hbp_matmul.48 = bf16[3072,6144]{1,0:T(8,128)(2,1)} custom-call("
          "bf16[3072,2048]{1,0:T(8,128)(2,1)S(1)} %bitcast.224, "
          "bf16[2048,6144]{1,0:T(8,128)(2,1)S(1)} %fusion.24), "
          'custom_call_target="tpu_custom_call", operand_layout_constraints='
          "{bf16[3072,2048]{1,0}, bf16[2048,6144]{1,0}}")
ATTN = ("%fa.4 = (bf16[192,1,128]{2,1,0:T(2,128)(2,1)S(1)}, f32[192,1,1]"
        "{2,1,0:T(1,128)}) custom-call(s32[12]{0:T(128)S(1)} %gte.416, "
        "s32[12]{0:T(128)S(1)} %gte.417, bf16[192,1,128]{2,1,0:T(2,128)(2,1)"
        "S(1)} %fusion.4, bf16[96,3584,128]{2,1,0:T(8,128)(2,1)} %bitcast.171,"
        " bf16[96,3584,128]{2,1,0:T(8,128)(2,1)} %bitcast.172), "
        'custom_call_target="tpu_custom_call"')


def test_programs_and_kernels_are_found_by_name():
    tr = _synthetic()
    assert [e.name for e in devtrace.module_events(tr, "decode_rows")] == [
        "jit_decode_rows(1)"]
    tr.ops["TPU:0"] += [Event(MATMUL, 70, 5), Event(ATTN, 80, 5)]
    assert [devtrace.kernel(e) for e in devtrace.kernel_events(
        tr, "hbp_matmul")] == ["hbp_matmul"]
    assert len(devtrace.kernel_events(tr, "fa")) == 1


def test_operand_shapes_come_from_the_hlo_text():
    assert devtrace.operand_shapes(Event(MATMUL, 0, 1)) == [
        ("bf16", (3072, 2048)), ("bf16", (2048, 6144))]
    assert [s for _, s in devtrace.operand_shapes(Event(ATTN, 0, 1))] == [
        (12,), (12,), (192, 1, 128), (96, 3584, 128), (96, 3584, 128)]
    assert devtrace.short_name(Event(MATMUL, 0, 1)) == \
        "hbp_matmul.48 bf16[3072,6144]"


def test_round_trip_through_json(tmp_path):
    tr = _synthetic()
    devtrace.save(tr, tmp_path / "t.json")
    back = devtrace.read(tmp_path / "t.json")
    assert back.to_json() == tr.to_json()


def _recorded():
    """0.1 s of a traced window on one TPU v5e: the 32B stage's longdoc
    cell, prefill launches running."""
    return devtrace.read(DATA / "longdoc_trace_slice.json")


def test_recorded_busy_time_matches_a_microsecond_grid():
    import numpy as np

    tr = _recorded()
    grid = np.zeros(int((tr.end_ns - tr.begin_ns) // 1000) + 1, bool)
    for e in tr.ops["TPU:0"]:
        a = max(int((e.start_ns - tr.begin_ns) // 1000), 0)
        b = min(int(-(-(e.end_ns - tr.begin_ns) // 1000)), len(grid))
        grid[a:b] = True
    n_ops = len(tr.ops["TPU:0"])
    assert devtrace.busy_s(tr) == pytest.approx(grid.sum() * 1e-6,
                                                abs=2 * n_ops * 1e-6)
    assert 0 < devtrace.busy_s(tr) <= tr.window_s


def test_recorded_matmuls_parse_and_stay_under_their_roofline():
    from types import SimpleNamespace

    import spec

    tr = _recorded()
    mms = devtrace.kernel_events(tr, "hbp_matmul")
    assert mms
    for e in mms:
        (_, (m, k)), (_, (k2, n)) = devtrace.operand_shapes(e)[:2]
        assert k == k2 and m % 16 == 0
    run = SimpleNamespace(trace=tr, peak=spec.peaks("TPU v5 lite"))
    share = spec.metric_reader("kernel.matmul.roofline")(run)
    assert 0 < share <= 100


def test_recorded_breakdown_is_bounded_and_sorted():
    b = devtrace.breakdown(_recorded())
    for key in ("device_ops", "idle_gaps"):
        assert 0 < len(b[key]) <= 10
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    gaps = [s for _, s in b["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
