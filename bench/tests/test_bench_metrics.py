"""Operation and byte counts from shapes, and the window arithmetic of the
end-to-end metrics."""
from types import SimpleNamespace

import pytest

import flops
import serving
import spec

CFG = spec.config("qwen3-1.7b")
REF = spec.reference(CFG["model_type"])
PEAK = spec.peaks("TPU v5 lite")
# Qwen3-1.7B's attention: 16 query heads of 128 in each of 28 layers
HEADS, HEAD_DIM, LAYERS = 16, 128, 28


def test_layer_params_match_the_published_model():
    # Qwen3-1.7B: 2.03 B parameters with the embedding, 1.72 B without
    # the tied head counted twice; 28 layers of attention and MLP
    per_layer = 2048 * (2048 + 2 * 1024) + 2048 * 2048 + 3 * 2048 * 6144
    assert REF.layer_matmul_params(CFG) == [per_layer] * LAYERS
    assert (sum(REF.layer_matmul_params(CFG)) + REF.head_params(CFG)
            == pytest.approx(1.72e9, rel=0.01))


def test_attention_flops_sum_every_causal_row():
    brute = sum(4 * HEADS * HEAD_DIM * (p + 1) * LAYERS
                for p in range(100, 356))
    assert flops.attention_flops(REF, CFG, 100, 356) == brute


def test_windowed_attention_flops_count_the_window_only():
    layers = [{"heads": 2, "kv_heads": 1, "head_dim": 8, "window": 5},
              None, {"heads": 2, "kv_heads": 1, "head_dim": 8,
                     "window": None}]
    ref = SimpleNamespace(layer_attention=lambda cfg: layers)
    brute = sum(4 * 2 * 8 * (min(p + 1, 5) + p + 1) for p in range(3, 12))
    assert flops.attention_flops(ref, {}, 3, 12) == brute


def test_chunked_prefill_adds_up_to_whole_prefill():
    whole = flops.prefill_flops(REF, CFG, 0, 768, logits_rows=3)
    parts = sum(flops.prefill_flops(REF, CFG, a, a + 256)
                for a in (0, 256, 512))
    assert parts == whole


def test_decode_token_is_a_one_row_prefill():
    assert (flops.decode_flops(REF, CFG, 1000)
            == flops.prefill_flops(REF, CFG, 999, 1000))


def test_matmul_and_roofline_bounds():
    f, b = flops.matmul(16, 2048, 6144)
    assert f == 2 * 16 * 2048 * 6144
    assert b == 2 * (16 * 2048 + 2048 * 6144 + 16 * 6144)
    # a decode-sized product is bound by memory, a prefill-sized one by
    # compute
    assert flops.least_time_s(f, b, PEAK) == b / PEAK["hbm_bytes_per_s"]
    f, b = flops.matmul(4096, 2048, 6144)
    assert flops.least_time_s(f, b, PEAK) == f / PEAK["bf16_flops_per_s"]


def test_attn_decode_call_reads_each_live_row_once():
    f, b = flops.attn_decode_call(REF, CFG, [10, 30])
    kv_heads = 8
    assert f == 4 * HEADS * HEAD_DIM * 40
    assert b == 2 * 40 * kv_heads * HEAD_DIM * 2 + 2 * 2 * HEADS * HEAD_DIM * 2


def _rec(uid, due, times, plen=100, max_new=None):
    return serving.Record(uid, plen, max_new or len(times), due, due, None,
                          list(times), times[-1] if times else None)


def _run(records, w0=10.0, w1=20.0, open_loop=True, drain_end=40.0):
    served = serving.Served(records, w0, w1, w1, drain_end, [], {}, {}, [])
    return SimpleNamespace(served=served, open_loop=open_loop,
                           tracked=served.tracked(open_loop), setup_s=3.0)


def test_ttft_counts_from_due_and_charges_the_unanswered():
    recs = [_rec(i, 10.0 + i, [10.0 + i + 0.1 * (i + 1)]) for i in range(9)]
    recs.append(_rec(9, 19.0, [], max_new=5))           # never answered
    recs.append(_rec(10, 9.0, [9.5]))                   # due before window
    run = _run(recs)
    ttft = [0.1 * (i + 1) for i in range(9)] + [40.0 - 19.0]
    want = serving.percentile(ttft, 90) * 1e3
    assert spec.metric_reader("ttft_p90_ms")(run) == pytest.approx(want)
    assert len(run.tracked) == 10


def test_itl_takes_gaps_ending_in_the_window():
    recs = [_rec(0, 0.0, [9.0, 10.5, 11.0, 11.0, 25.0]),
            _rec(1, 0.0, [12.0, 12.2])]
    gaps = [1.5, 0.5, 0.0, 0.2]          # 9->10.5 ends inside; 11->25 not
    want = serving.percentile(gaps, 95) * 1e3
    assert spec.metric_reader("itl_p95_ms")(_run(recs)) == pytest.approx(want)


def test_tok_s_counts_prompts_at_first_token_and_outputs_in_window():
    recs = [_rec(0, 0.0, [9.0, 10.5, 11.0], plen=500),   # prompt before w0
            _rec(1, 0.0, [12.0, 19.9, 20.1], plen=300),  # prompt inside
            _rec(2, 0.0, [20.5], plen=700)]              # after w1
    want = (300 + 2 + 2) / 10.0
    assert spec.metric_reader("tok_s")(_run(recs)) == pytest.approx(want)


def test_backlog_tracks_requests_with_tokens_in_the_window():
    recs = [_rec(0, 5.0, [9.0]), _rec(1, 5.0, [9.0, 15.0]),
            _rec(2, 15.0, [21.0])]
    assert [r.uid for r in _run(recs, open_loop=False).tracked] == [1]


def test_setup_reader():
    assert spec.metric_reader("setup_s")(_run([])) == 3.0
