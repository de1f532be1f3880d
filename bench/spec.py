"""Find the benchmark's parts by name.

Everything that belongs to one configuration, traffic mix, cell or metric
sits in a file of its own under this directory, named after it:

    configs/<config>.json     sizes as published, the cut, the engine geometry
    traffic/<traffic>.json    parameters of one mix, read by ``traffic.py``
    arrivals/<process>.py     an arrival process a mix names
    lengths/<dist>.py         a length distribution a mix names
    workloads/<cell>.json     the cell: its config, its traffic, its rate,
                              warm-up, drain and correctness limits
    metrics/<metric>.py       a reader: ``read(run) -> float | None``
    reference/<model_type>.py a model family's whole tie to the harness

A new one is a new file plus its entry in ``BENCHMARK.json``; no file that
is already here needs an edit.  The harness names no family: a config's
``model_type`` finds its module, which gives, each from the config file's
keys (with ``reduced`` and ``assumed`` where a cut needs them):

    model_config(cfg)         the program's ``ModelConfig``, every field
    layout(cfg), NORMS        the weights' leaves, stacks and dtypes, and
                              which leaves are norms (see ``weights.py``)
    layer_matmul_params(cfg), layer_attention(cfg), head_params(cfg)
                              what the operation counts read (``flops.py``)
    logits(tokens, rows, cfg, top, layer, quant=None)
                              the plain float32 reference (``check.py``)
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent


def _json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    with open(path) as f:
        return json.load(f)


def config(name: str, root: Path = BENCH) -> dict:
    return _json(root / "configs" / f"{name}.json")


def traffic(name: str, root: Path = BENCH) -> dict:
    return _json(root / "traffic" / f"{name}.json")


def workload(name: str, root: Path = BENCH) -> dict:
    """A cell's file, with its config and traffic files loaded beside it
    (``cfg`` and ``mix``)."""
    cell = _json(root / "workloads" / f"{name}.json")
    cell["name"] = name
    cell["cfg"] = config(cell["config"], root)
    cell["mix"] = traffic(cell["traffic"], root)
    return cell


def _module(path: Path, modname: str):
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark module: {path}")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: Path = BENCH):
    """The ``read`` function of ``metrics/<name>.py``."""
    return _named("metrics", name, root).read


def _named(kind: str, name: str, root: Path):
    return _module(root / kind / f"{name}.py",
                   f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"))


def reference(model_type: str, root: Path = BENCH):
    """``reference/<model_type>.py``: the module of the config's family
    (a missing one raises, naming the file)."""
    return _named("reference", model_type, root)


def arrivals(process: str, root: Path = BENCH):
    return _named("arrivals", process, root)


def lengths(dist: str, root: Path = BENCH):
    return _named("lengths", dist, root)


def benchmark(path: Path = REPO / "BENCHMARK.json") -> dict:
    return _json(path)


def metrics_for(spec: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: its end-to-end metrics with
    the profiler off, its per-layer metrics with it on.  A metric with a
    ``workloads`` list belongs to those cells only."""
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def peaks(kind: str, root: Path = BENCH) -> dict:
    """Published peaks of one chip, by its ``device_kind``.  A kind the
    table lacks is an error, never a default."""
    table = _json(root / "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks known for device kind {kind!r}; "
                       f"add it to {root / 'peaks.json'}")
    return table[kind]
