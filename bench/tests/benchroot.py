"""A benchmark directory of tiny cells, built on the fly for the CPU tests.

It holds its own configs, traffic mixes, cells and a peaks table for the
CPU, and copies of the benchmark's metric readers, references, arrival
processes and length distributions, so a run goes through every file the
chip's runs go through.
"""
from __future__ import annotations

import copy
import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

# Qwen3's layout at a size the CPU serves in seconds; wide enough that a
# lower precision flips some near-ties over a few hundred tokens
TINY = {"num_hidden_layers": 8, "hidden_size": 256, "num_attention_heads": 8,
        "num_key_value_heads": 4, "head_dim": 32, "intermediate_size": 512,
        "vocab_size": 16384}
ENGINE = {"max_batch": 4, "max_len": 128, "chunk": 16}
CHAT = {"arrivals": {"process": "poisson"}, "block": 8,
        "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.6,
                   "min": 8, "max": 64},
        "output": {"dist": "lognormal", "median": 24, "sigma": 0.4,
                   "min": 8, "max": 48}}
DOC = {"arrivals": {"process": "backlog", "waiting_per_slot": 2},
       "block": 8,
       "prompt": {"dist": "uniform", "min": 20, "max": 60},
       "output": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                  "min": 3, "max": 12}}
# Served tokens' widest gap on the CPU at this size, seeds 100-111 with an
# 8-s window: 0 to 6.4e-3; the fp8 control reads 5.5e-2 to 1.4e-1 over the
# same runs.  Eight layers, because with four the control's error stayed
# within rounding on one seed (fp8 read 4.3e-3).
CHECK = {"sample_tokens": 600, "max_requests": 24,
         "limits": {"logit_gap": 0.02, "min_served_tokens": 50}}


def tiny_config(**over) -> dict:
    cfg = json.loads((BENCH / "configs" / "qwen3-1.7b.json").read_text())
    # the chip cells' engine settings, at the tiny geometry
    cfg.update(TINY, name="tiny", engine=dict(cfg["engine"], **ENGINE),
               reduced=sorted(TINY))
    cfg.update(over)
    return cfg


def spec_for(cells: dict) -> dict:
    """BENCHMARK.json's metrics, with the tiny cells as its workloads and
    every metric reported in every one of them."""
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    spec["workloads"] = [{"name": n, "config": c["config"],
                          "traffic": c["traffic"], "chips": 1, "why": "test"}
                         for n, c in cells.items()]
    # the chat cell stands for the open-loop cell, the backlog for the other
    names = {"qwen3-1.7b.chat": "tiny.chat", "qwen3-32b-l6.longdoc": "tiny.doc"}
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [names[w] for w in m["workloads"]]
    return spec


def build(root: Path, seconds_warm: float = 1.0) -> tuple[Path, dict]:
    root = Path(root)
    for sub in ("metrics", "reference", "arrivals", "lengths"):
        shutil.copytree(BENCH / sub, root / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for sub in ("configs", "traffic", "workloads"):
        (root / sub).mkdir()
    (root / "configs" / "tiny.json").write_text(json.dumps(tiny_config()))
    (root / "traffic" / "chat.json").write_text(json.dumps(CHAT))
    (root / "traffic" / "doc.json").write_text(json.dumps(DOC))
    # the open-loop rate stays well under what the tiny engine serves on a
    # CPU shared with other test workers, so every request due finishes
    cells = {
        "tiny.chat": {"config": "tiny", "traffic": "chat", "rate_per_s": 1.5,
                      "warmup_s": seconds_warm, "drain_max_s": 120,
                      "check": copy.deepcopy(CHECK)},
        "tiny.doc": {"config": "tiny", "traffic": "doc",
                     "warmup_s": seconds_warm, "drain_max_s": 120,
                     "check": copy.deepcopy(CHECK)},
    }
    for name, cell in cells.items():
        (root / "workloads" / f"{name}.json").write_text(json.dumps(cell))
    (root / "peaks.json").write_text(json.dumps(
        {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
                 "source": "test stand-in for the CPU"}}))
    return root, spec_for(cells)
