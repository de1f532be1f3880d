"""Whole runs of tiny cells on the CPU: the harness as the chip runs it,
past its look for a chip, with the timed path sound, broken, or replaced
by a lower precision."""
import json
import time

import jax
import pytest

import benchroot
import run


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return benchroot.build(tmp_path_factory.mktemp("bench"))


@pytest.fixture(autouse=True)
def _cpu_is_the_chip(monkeypatch):
    monkeypatch.setattr(run, "require_accelerator",
                        lambda chips: jax.devices()[:chips])


def _run(bench, cell, seed, trace=False, seconds=3.0, **kw):
    root, spec = bench
    return run.run_cell(cell, seed, seconds, trace, root=root, spec=spec,
                        t_start=time.perf_counter(), **kw)


@pytest.mark.parametrize("cell", ["tiny.chat", "tiny.doc"])
def test_sound_run_is_correct(bench, cell, capsys):
    out = _run(bench, cell, 2**31 + 101)
    # set-up built every program the window uses
    assert "programs built inside the window: 0" in capsys.readouterr().err
    json.dumps(out)
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    want = {"itl_p95_ms", "tok_s", "setup_s"} | (
        {"ttft_p90_ms"} if cell == "tiny.chat" else set())
    assert set(out["metrics"]) == want
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "compared"


def test_traced_run_reports_layers_not_end_to_end(bench):
    out = _run(bench, "tiny.doc", 7, trace=True)
    assert out["correct"]
    assert not {"tok_s", "itl_p95_ms"} & set(out["metrics"])
    assert out["metrics"]["engine.decode_rows"]["value"] > 0
    assert 0 < out["metrics"]["step.mfu"]["value"]
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def _alter_tokens(monkeypatch):
    """A token altered where the engine produces it."""
    from repro.launch.engine import Engine

    emit = Engine._emit
    monkeypatch.setattr(Engine, "_emit", lambda self, i, tok: emit(
        self, i, (tok + 1) % self.cfg.vocab_size))


def _keep_state(monkeypatch):
    """A decode step that returns its cache unchanged."""
    from repro.models.dense import DenseLM

    step = DenseLM.decode_step

    def unchanged(self, params, tokens, pos, cache, extras=None):
        logits, _ = step(self, params, tokens, pos, cache, extras)
        return logits, cache

    monkeypatch.setattr(DenseLM, "decode_step", unchanged)


@pytest.mark.parametrize("fault", [_alter_tokens, _keep_state])
def test_broken_timed_path_is_not_correct(bench, monkeypatch, fault):
    fault(monkeypatch)
    out = _run(bench, "tiny.chat", 2**31 + 101)
    c = out["compared"]["logit_gap"]
    assert not out["correct"] and c["value"] > c["limit"], c


@pytest.mark.parametrize("seed", [101, 102])
def test_lower_precision_in_the_programs_place_is_not_correct(bench, seed):
    """The control: fp8 operands in the reference, read at the served
    positions and judged by the cell's own comparison, come out not
    correct, on the same run whose own served tokens pass."""
    out = _run(bench, "tiny.chat", seed, seconds=8.0, controls=("fp8",))
    assert out["correct"]
    ok, compared = out["controls"]["fp8"]
    c = compared["logit_gap"]
    assert not ok and c["value"] > c["limit"], compared
