"""The traffic generator, and finding the benchmark's parts by name."""
import json
import shutil

import numpy as np
import pytest

import spec
import traffic

BIG_SEED = 2**31 + 12345


def _mix(name):
    return spec.traffic(name)


def _take(mix, seed, n, rate=None):
    return traffic.Stream(mix, seed, 151936, rate).take(n)


@pytest.mark.parametrize("name,rate", [("chat", 0.7), ("longdoc", None)])
def test_stream_is_a_function_of_the_seed(name, rate):
    mix = _mix(name)
    a, b = _take(mix, BIG_SEED, 40, rate), _take(mix, BIG_SEED, 40, rate)
    for x, y in zip(a, b):
        assert (x.due_s, x.max_new) == (y.due_s, y.max_new)
        np.testing.assert_array_equal(x.prompt, y.prompt)
    c = _take(mix, BIG_SEED + 1, 40, rate)
    assert any(not np.array_equal(x.prompt[:8], y.prompt[:8])
               for x, y in zip(a, c))


@pytest.mark.parametrize("name,rate", [("chat", 0.7), ("longdoc", None)])
def test_every_block_offers_the_same_work(name, rate):
    """Every seed gets each block's lengths; in an open mix also the same
    sizes at the same times, with other prompt tokens."""
    mix = _mix(name)
    k = mix["block"]
    runs = {seed: _take(mix, seed, 3 * k, rate) for seed in (0, 7, BIG_SEED)}
    for reqs in runs.values():
        for b in range(3):
            blk = reqs[b * k:(b + 1) * k]
            assert sorted(len(r.prompt) for r in blk) == sorted(
                traffic.lengths(mix["prompt"], k))
            assert sorted(r.max_new for r in blk) == sorted(
                traffic.lengths(mix["output"], k))
    work = {seed: [(r.due_s, len(r.prompt), r.max_new) for r in reqs]
            for seed, reqs in runs.items()}
    assert (work[0] == work[BIG_SEED]) == bool(rate)
    assert not np.array_equal(runs[0][0].prompt[:8], runs[7][0].prompt[:8])


def test_chat_lengths_and_poisson_arrivals():
    mix = _mix("chat")
    rate = 0.7
    reqs = _take(mix, 3, 500 * mix["block"], rate)
    plen = np.array([len(r.prompt) for r in reqs])
    out = np.array([r.max_new for r in reqs])
    assert (mix["output"]["median"], mix["output"]["max"]) == (128, 256)
    for got, dist in ((plen, mix["prompt"]), (out, mix["output"])):
        assert dist["min"] <= got.min() and got.max() <= dist["max"]
        assert abs(np.median(got) / dist["median"] - 1) < 0.1
    due = np.array([r.due_s for r in reqs])
    gaps = np.diff(np.concatenate([[0.0], due]))
    assert np.all(gaps > 0)
    # exponential gaps: mean 1/rate, e^-1 of them above the mean, and the
    # spread of a Poisson count: arrivals per 10 s vary as much as they
    # average (a schedule of fixed spacing would hardly vary at all)
    assert abs(gaps.mean() * rate - 1) < 0.05
    assert abs((gaps > 1 / rate).mean() - np.exp(-1)) < 0.03
    counts = np.bincount((due // 10).astype(int))[:-1]
    assert counts.var() / counts.mean() == pytest.approx(1.0, abs=0.15)


def test_longdoc_is_a_closed_backlog_of_long_prompts():
    mix = _mix("longdoc")
    stream = traffic.Stream(mix, 5, 151936)
    assert not stream.open and stream.waiting(16) == 32
    reqs = stream.take(4 * mix["block"])
    assert all(r.due_s is None for r in reqs)
    plen = np.array([len(r.prompt) for r in reqs])
    out = np.array([r.max_new for r in reqs])
    assert plen.min() >= 2048 and plen.max() <= 3584
    assert abs(plen.mean() - (2048 + 3584) / 2) < 16
    assert out.min() >= 8 and out.max() <= 128
    assert abs(np.median(out) / 32 - 1) < 0.1


def test_open_loop_needs_a_rate_and_seeds_are_whole():
    with pytest.raises(ValueError):
        traffic.Stream(_mix("chat"), 1, 100)
    with pytest.raises(ValueError):
        traffic.Stream(_mix("longdoc"), -1, 100)


def test_new_parts_are_found_by_name(tmp_path):
    """A config, a traffic mix, a cell and a metric dropped in as new files
    are found by their names; no existing file changes."""
    root = tmp_path / "bench"
    shutil.copytree(spec.BENCH, root,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    cfg = dict(spec.config("qwen3-1.7b"), name="new-model")
    (root / "configs" / "new-model.json").write_text(json.dumps(cfg))
    (root / "arrivals" / "every.py").write_text(
        "OPEN = True\n"
        "def gaps(params, rate, rng, n):\n"
        "    return [params['every_s']] * n\n")
    (root / "lengths" / "fixed.py").write_text(
        "def ppf(params, q):\n    return [params['n']] * len(q)\n")
    fixed = {"dist": "fixed", "n": 9, "min": 1, "max": 99}
    (root / "traffic" / "bursty.json").write_text(json.dumps(
        {"arrivals": {"process": "every", "every_s": 0.5}, "block": 4,
         "classes": [{"share": 0.75, "prompt": fixed, "output": fixed},
                     {"share": 0.25, "prompt": dict(fixed, n=50),
                      "output": dict(fixed, n=3)}]}))
    (root / "workloads" / "new-model.bursty.json").write_text(json.dumps(
        {"config": "new-model", "traffic": "bursty", "rate_per_s": 2.0,
         "warmup_s": 1, "drain_max_s": 5, "check": {}}))
    (root / "metrics" / "queue.wait_ms.py").write_text(
        "def read(run):\n    return 42.0\n")
    cell = spec.workload("new-model.bursty", root)
    assert cell["cfg"]["name"] == "new-model"
    assert cell["mix"]["block"] == 4
    reqs = traffic.Stream(cell["mix"], 1, 100, 2.0, root).take(8)
    assert [r.due_s for r in reqs] == [0.5 * (i + 1) for i in range(8)]
    assert sorted((len(r.prompt), r.max_new) for r in reqs) == (
        [(9, 9)] * 6 + [(50, 3)] * 2)
    assert spec.metric_reader("queue.wait_ms", root)(None) == 42.0
    assert spec.reference(cell["cfg"]["model_type"], root).logits
    for p, data in before.items():
        assert p.read_bytes() == data
    bench = {"end_to_end": [{"name": "tok_s"},
                            {"name": "ttft_p90_ms", "workloads": ["a"]}],
             "per_layer": [{"name": "queue.wait_ms", "workloads": ["b"]}]}
    assert [m["name"] for m in spec.metrics_for(bench, "b", False)] == ["tok_s"]
    assert [m["name"] for m in spec.metrics_for(bench, "b", True)] == [
        "queue.wait_ms"]


def test_every_benchmark_entry_has_its_files():
    bench = spec.benchmark()
    for w in bench["workloads"]:
        cell = spec.workload(w["name"])
        assert (cell["config"], cell["traffic"]) == (w["config"], w["traffic"])
    for c in bench["configs"]:
        assert spec.config(c["name"])["reduced"] == c["reduced"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))


def test_unknown_device_kind_has_no_peaks():
    assert spec.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        spec.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("shares,counts", [
    ([1.0], [20]), ([0.8, 0.2], [16, 4]), ([0.5, 0.3, 0.2], [4, 2, 1]),
    ([0.45, 0.55], [3, 4])])
def test_class_counts_fill_the_block(shares, counts):
    block = sum(counts)
    assert traffic.class_counts(shares, block) == counts


def test_class_shares_must_add_up():
    mix = dict(_mix("chat"), classes=[
        {"share": 0.5, "prompt": {}, "output": {}}])
    with pytest.raises(ValueError, match="shares"):
        traffic.classes(mix)
