"""Measured autotune layer over the resource-oblivious planner.

The planner (``repro.kernels.planner``) derives every tile shape analytically
from queried device parameters pushed through the costmodel envelopes.  The
envelopes are asymptotically right but carry constant factors (the one-third
``_budget`` slack, the 2t-deep kv panel) that real machines disagree with —
exactly the regime the companion RWS/false-sharing analysis (arXiv:1103.4142)
identifies.  This module closes the loop **without touching any kernel
signature**: kernels stay oblivious, the runtime *measures* each device's
constants and replays them.

Three pieces:

``candidates(op, *args)``
    A power-of-two ladder of tile plans around the planner's analytic point,
    filtered by the kernels' divisibility constraints and the fast-memory
    envelope (every candidate's working set fits the queried ``fast_bytes``).

``search(op, *args)``
    Times each candidate on the real kernel (compile excluded, median-of-k,
    ``block_until_ready``) and records the winner in the persisted table.

``overlay(op, args)``
    The dispatch-time hook: a tuned-table hit for the current
    ``(device_kind, op, shape_class, dtype)`` key overlays the analytic plan
    (snapped back to the actual shape's divisibility), explicit overrides
    still win.  Controlled by the mode knob:

      * ``off``    — analytic plans only (the bare-dispatch default);
      * ``replay`` — overlay persisted measurements; a cold cache is a no-op;
      * ``search`` — like replay, but a table miss on concrete (non-traced)
        arrays triggers an in-line search whose winner is persisted.

    ``REPRO_AUTOTUNE`` sets the process default; launchers call
    :func:`startup` (which resolves ``RunOptions.autotune``) and tests use
    :func:`mode_scope`.

Tables are JSON files under ``REPRO_TUNE_DIR`` (default
``~/.cache/repro/autotune``), one per sanitized ``device_kind``.  Corrupt or
unknown-format files are ignored, never fatal.

Table keys carry the op's *semantic* flags alongside the shape class
(``attention`` keys causal, window, and a decode marker — sq != sk;
``matmul`` keys the planner-selected backend), so masking regimes,
cached-decode shapes, and Strassen-vs-classical matmuls never share one
measured optimum.  Tables are also stamped with ``jax.__version__`` on
write; a table written by a different jaxlib/toolchain (or an older key
format — table versions 1 and 2) is treated as a cold cache.

Beyond tile sizes, v3 entries may tune *variant* knobs: the matmul backend
("classical" | "strassen") and its recursion ``cutoff`` (the measured
crossover can overrule the modeled one in either direction), and the
``morton`` grid-schedule flag on matmul/transpose.  On an exact-key miss,
``overlay`` interpolates: it borrows the nearest recorded shape_class for
the same ``(device_kind, op, dtype, flags)`` (snapped back to the actual
shape's divisibility) instead of going cold, logging once per borrowed key.
"""
from __future__ import annotations

import contextlib
import itertools
import json
import logging
import math
import os
import re
import statistics
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.kernels import planner

log = logging.getLogger("repro.autotune")

MODES = ("off", "replay", "search")
_DEFAULT_DIR = "~/.cache/repro/autotune"
# v2: semantic flags joined the key format; v3: matmul keys its derived
# backend flag and plans may carry variant knobs (backend/cutoff/morton).
# Older tables are ignored (cold)
_TABLE_VERSION = 3

_mode_override: Optional[str] = None
# (tune_dir, device_kind) -> entries dict; cleared by clear_cache()
_TABLE_CACHE: dict[tuple[str, str], dict] = {}

# per-op semantic kwargs folded into the table key (masking regime changes
# the measured optimum even at one shape class), with the kernel-signature
# defaults so omitted kwargs key identically to explicitly-passed defaults
_SEM_FLAGS: dict[str, dict] = {"attention": {"causal": True, "window": 0}}


# ---------------------------------------------------------------------------
# mode knob
# ---------------------------------------------------------------------------

def resolve_mode(value: Optional[str] = None) -> str:
    """Launcher-side resolution: explicit value > ``REPRO_AUTOTUNE`` >
    ``replay`` (replay on a cold cache is a no-op, so it is the safe
    startup default).  Raises on unknown modes so typos surface early."""
    m = value or os.environ.get("REPRO_AUTOTUNE") or "replay"
    if m not in MODES:
        raise ValueError(f"unknown autotune mode {m!r}; expected one of {MODES}")
    return m


def mode() -> str:
    """The active mode for bare dispatch: an autotune field set on the
    ambient :class:`~repro.kernels.policy.ExecutionPolicy` (a scoped
    ``policy.apply(autotune=...)`` or the RunOptions compat shim) wins,
    then the process override, then ``REPRO_AUTOTUNE``, else ``off``
    (analytic plans only — benchmarks and tests see the pure planner
    unless they opt in)."""
    from repro.kernels import policy

    pol = policy.current().autotune
    if pol is not None:
        return pol
    if _mode_override is not None:
        return _mode_override
    env = os.environ.get("REPRO_AUTOTUNE", "off")
    return env if env in MODES else "off"


def set_mode(m: Optional[str]) -> None:
    """Set (or with ``None`` clear) the process-wide mode override."""
    global _mode_override
    if m is not None and m not in MODES:
        raise ValueError(f"unknown autotune mode {m!r}; expected one of {MODES}")
    _mode_override = m


@contextlib.contextmanager
def mode_scope(m: Optional[str]):
    """Temporarily pin the mode (tests, benchmark arms)."""
    global _mode_override
    prev = _mode_override
    set_mode(m)
    try:
        yield
    finally:
        _mode_override = prev


def startup(m: Optional[str] = None) -> str:
    """Launcher hook (serve/train): resolve and pin the mode **process-wide**
    (every subsequent dispatch in this process replays, by design — the
    launcher owns the runtime policy), and preload the current device's
    table so the first dispatch trace pays no IO."""
    resolved = resolve_mode(m)
    set_mode(resolved)
    if resolved != "off":
        dp = planner.device_params()
        log.info("autotune %s: %d tuned plan(s) for %s",
                 resolved, len(load_table(dp.kind)), dp.kind)
    return resolved


def provenance() -> dict:
    """Tuned-table provenance for startup logs and benchmark JSON: where the
    replay table lives, whether it exists, and how many plans it holds for
    this device kind — so a serving/benchmark number can always be traced
    back to the exact tile table (or its absence) it ran with."""
    dp = planner.device_params()
    path = table_path(dp.kind)
    return {
        "mode": resolve_mode(None),
        "device_kind": dp.kind,
        "table": str(path),
        "table_exists": path.exists(),
        "tuned_plans": len(load_table(dp.kind)),
    }


# ---------------------------------------------------------------------------
# table keys
# ---------------------------------------------------------------------------

def _pow2_ceil(x: int) -> int:
    return 1 << max(int(x) - 1, 0).bit_length()


def shape_class(*args) -> str:
    """Power-of-two bucketed shape signature: nearby shapes share one table
    entry; :func:`snap_plan` restores exact divisibility at replay time."""
    return "_".join("x".join(str(_pow2_ceil(d)) for d in a.shape) or "scalar"
                    for a in args)


def sem_class(op: str, args, kwargs: Optional[dict] = None) -> str:
    """Semantic-flag suffix of the table key: the op's masking/regime kwargs
    (static Python scalars only — traced values key as ``?``), plus derived
    shape-regime markers (attention: ``decode`` when sq != sk; matmul: the
    planner-selected ``backend``, so Strassen and classical shapes never
    share a measured optimum)."""
    kwargs = kwargs or {}
    parts = []
    for flag, default in _SEM_FLAGS.get(op, {}).items():
        v = kwargs.get(flag)
        if v is None:
            v = default  # omitted == kernel default: one key per config
        if isinstance(v, (bool, int, str)):
            parts.append(f"{flag}={v}")
        else:
            parts.append(f"{flag}=?")
    if op == "attention":
        parts.append(f"decode={args[0].shape[1] != args[1].shape[1]}")
        # a quantized KV stream (int8 cache vs f32 q) re-shapes the optimum
        # (4x deeper panels) — never share an entry with the uniform-dtype
        # regime
        if args[1].dtype != args[0].dtype:
            parts.append(f"kv_dtype={jnp.dtype(args[1].dtype).name}")
    if op == "matmul":
        parts.append(f"backend={kwargs.get('backend') or 'classical'}")
    return ",".join(parts)


def entry_key(op: str, *args, kwargs: Optional[dict] = None) -> str:
    base = f"{op}|{shape_class(*args)}|{jnp.dtype(args[0].dtype).name}"
    sem = sem_class(op, args, kwargs)
    return f"{base}|{sem}" if sem else base


# ---------------------------------------------------------------------------
# persisted tables (one JSON per device_kind under REPRO_TUNE_DIR)
# ---------------------------------------------------------------------------

def tune_dir() -> Path:
    return Path(os.environ.get("REPRO_TUNE_DIR")
                or os.path.expanduser(_DEFAULT_DIR))


def table_path(kind: Optional[str] = None) -> Path:
    kind = kind or planner.device_params().kind
    safe = re.sub(r"[^A-Za-z0-9._-]+", "_", kind) or "device"
    return tune_dir() / f"{safe}.json"


def _valid_plan_value(v) -> bool:
    # tiles are positive ints; variant knobs are the morton bool and the
    # matmul backend string
    if isinstance(v, bool):
        return True
    if isinstance(v, int):
        return v > 0
    return isinstance(v, str) and v in ("classical", "strassen")


def _valid_entry(entry) -> bool:
    return (isinstance(entry, dict) and isinstance(entry.get("plan"), dict)
            and len(entry["plan"]) > 0
            and all(_valid_plan_value(v) for v in entry["plan"].values()))


def load_table(kind: Optional[str] = None) -> dict:
    """The (cached) entries dict for one device kind.  Missing, corrupt,
    unknown-format, or stale files (a different table version or a
    ``jax_version`` stamp from another jaxlib/toolchain — tuned timings do
    not survive compiler upgrades) all yield an empty table — replay
    degrades to the analytic plan, it never takes the process down."""
    kind = kind or planner.device_params().kind
    cache_key = (str(tune_dir()), kind)
    hit = _TABLE_CACHE.get(cache_key)
    if hit is not None:
        return hit
    path = table_path(kind)
    entries: dict = {}
    try:
        raw = json.loads(path.read_text())
        if not (isinstance(raw, dict) and raw.get("version") == _TABLE_VERSION
                and isinstance(raw.get("entries"), dict)):
            log.warning("autotune: ignoring table %s (unknown format)", path)
        elif raw.get("jax_version") != jax.__version__:
            log.warning("autotune: ignoring table %s (tuned under jax %s, "
                        "running %s — treating as cold)", path,
                        raw.get("jax_version"), jax.__version__)
        else:
            entries = {k: v for k, v in raw["entries"].items()
                       if _valid_entry(v)}
    except FileNotFoundError:
        pass
    except (OSError, ValueError) as exc:  # json.JSONDecodeError is ValueError
        log.warning("autotune: ignoring corrupt table %s (%s)", path, exc)
    _TABLE_CACHE[cache_key] = entries
    return entries


def save_table(kind: Optional[str] = None) -> Path:
    kind = kind or planner.device_params().kind
    entries = load_table(kind)
    path = table_path(kind)
    path.parent.mkdir(parents=True, exist_ok=True)
    payload = {"version": _TABLE_VERSION, "device_kind": kind,
               "jax_version": jax.__version__,
               "entries": {k: entries[k] for k in sorted(entries)}}
    path.write_text(json.dumps(payload, indent=2) + "\n")
    return path


def clear_cache() -> None:
    """Drop the in-process table cache (tests that redirect REPRO_TUNE_DIR)."""
    _TABLE_CACHE.clear()
    _INTERP_LOGGED.clear()


# ---------------------------------------------------------------------------
# per-op tuning metadata: which axis each tile kwarg divides, and the
# working-set model the envelope filter checks against fast_bytes
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class OpTuneInfo:
    """dims(*args) maps each tile kwarg to the axis size it must divide;
    working_set(plan, *args) models the plan's resident bytes (tile kwargs
    only).  ``variants(base, *args, dp=...)`` — when set — returns the
    non-tile alternatives to cross with the tile ladder (backend/cutoff,
    schedule flags), base (the analytic choice) first; ``variant_keys``
    names the plan keys that replay verbatim instead of snapping."""

    dims: Callable[..., dict]
    working_set: Callable[..., int]
    variants: Optional[Callable[..., list]] = None
    variant_keys: tuple = ()


def _scan_dims(x):
    return {"block": x.shape[-1]}


def _scan_ws(plan, x):
    rows = x.shape[0] if x.ndim > 1 else 1
    return planner.scan_working_set(plan["block"], jnp.dtype(x.dtype).itemsize,
                                    rows)


def _matmul_dims(a, b):
    return {"bm": a.shape[0], "bk": a.shape[1], "bn": b.shape[1]}


def _matmul_ws(plan, a, b):
    return planner.matmul_working_set(plan["bm"], plan["bn"], plan["bk"],
                                      jnp.dtype(a.dtype).itemsize)


def _transpose_dims(x):
    m, n = x.shape
    return {"bt": m if m == n else math.gcd(m, n)}


def _transpose_ws(plan, x):
    return planner.transpose_working_set(plan["bt"],
                                         jnp.dtype(x.dtype).itemsize)


def _attention_dims(q, k, v):
    return {"q_block": q.shape[1], "kv_block": k.shape[1]}


def _attention_ws(plan, q, k, v):
    # quantized KV: narrower k/v panels
    return planner.attention_working_set(
        plan["q_block"], plan["kv_block"], q.shape[2],
        jnp.dtype(q.dtype).itemsize, jnp.dtype(k.dtype).itemsize)


def _matmul_variants(base, a, b, dp=None):
    """Schedule alternatives around the requested matmul: flip the
    ``morton`` grid flag; under a Strassen request also walk its cutoff one
    octave each way (one octave under the modeled gate when the planner
    would not recurse: leaves of n/2) and offer the classical schedule.  A
    classical request never tries Strassen: that schedule mixes rows, so
    it changes what a row computes, and runs only where a caller asks."""
    base = dict(base)
    out = [base]
    n = b.shape[1]
    out.append({**base, "morton": False})
    if base.get("backend") == "strassen":
        cut = int(base.get("cutoff") or n)
        for c in (cut * 2, cut // 2):
            if 64 <= c < n and c != cut:
                out.append({**base, "cutoff": c})
        out.append({"backend": "classical"})
    return out


def _transpose_variants(base, x, dp=None):
    return [dict(base), {**base, "morton": False}]


def _fft_dims(x):
    return {"n1": x.shape[-1]}


def _fft_ws(plan, x):
    n = x.shape[-1]
    n1 = plan["n1"]
    n2 = max(n // max(n1, 1), 1)
    # the two dense DFT factor matrices, (real, imag) f32 each
    return 8 * (n1 * n1 + n2 * n2)


_TUNE: dict[str, OpTuneInfo] = {
    "scan": OpTuneInfo(_scan_dims, _scan_ws),
    "matmul": OpTuneInfo(_matmul_dims, _matmul_ws, variants=_matmul_variants,
                         variant_keys=("backend", "cutoff", "morton")),
    "transpose": OpTuneInfo(_transpose_dims, _transpose_ws,
                            variants=_transpose_variants,
                            variant_keys=("morton",)),
    "attention": OpTuneInfo(_attention_dims, _attention_ws),
    "fft": OpTuneInfo(_fft_dims, _fft_ws),
}


def tunable_ops() -> list[str]:
    return sorted(_TUNE)


def variant_keys(op: str) -> tuple:
    """The op's non-tile plan knobs (backend/cutoff/morton).  Dispatch feeds
    forced variant overrides back into the table lookup through this, so a
    call that pins e.g. ``backend="classical"`` replays the classical entry,
    not the one keyed by the planner's own choice."""
    info = _TUNE.get(op)
    return info.variant_keys if info else ()


# ---------------------------------------------------------------------------
# candidate generation
# ---------------------------------------------------------------------------

def snap_plan(op: str, args, plan: dict) -> dict:
    """Clamp a tuned plan (possibly recorded for a same-class neighbour
    shape) back to the kernels' divisibility constraints: each tile becomes
    the largest divisor of its axis not exceeding the tuned value; variant
    knobs (backend/cutoff/morton) replay verbatim — the kernels gate their
    own eligibility."""
    info = _TUNE[op]
    dims = info.dims(*args)
    out = {}
    for k, v in plan.items():
        if k in dims:
            out[k] = planner.divisor_tile(dims[k], int(v))
        elif k in info.variant_keys:
            out[k] = v
    return out


def candidates(op: str, *args, dp: Optional[planner.DeviceParams] = None,
               max_candidates: int = 16, span: int = 2,
               variant: Optional[dict] = None) -> list[dict]:
    """Power-of-two ladder around the analytic plan: each tile kwarg ranges
    over factor 2**±``span`` of its planned value (snapped to divisors of its
    axis), the cross product is filtered by the fast-memory envelope, crossed
    with the op's variant alternatives (backend/cutoff, morton — see
    ``OpTuneInfo.variants``), and ranked by log-distance from the analytic
    point (each variant hop counts one octave).  ``variant`` holds the
    call's requested variant knobs (e.g. ``backend="strassen"``): the
    alternatives are taken around them.  The analytic plan is always
    candidate 0."""
    from repro.kernels import registry  # the layer below; lazy to stay acyclic

    spec = registry.get(op)
    info = _TUNE[op]
    dp = dp or planner.device_params()
    analytic = {**spec.plan(*args, dp=dp), **(variant or {})}
    dims = info.dims(*args)
    tile_analytic = {k: v for k, v in analytic.items() if k in dims}
    variant_analytic = {k: v for k, v in analytic.items() if k not in dims}

    ladders: dict[str, list[int]] = {}
    for key, base in tile_analytic.items():
        vals = set()
        for shift in range(-span, span + 1):
            target = base << shift if shift >= 0 else max(base >> -shift, 1)
            vals.add(planner.divisor_tile(dims[key], target))
        ladders[key] = sorted(vals)

    keys = sorted(ladders)
    tile_plans = [tile_analytic]
    for combo in itertools.product(*(ladders[k] for k in keys)):
        plan = dict(zip(keys, combo))
        if plan == tile_analytic:
            continue
        if info.working_set(plan, *args) > dp.fast_bytes:
            continue
        tile_plans.append(plan)

    variants = ([dict(variant_analytic)] if info.variants is None
                else info.variants(variant_analytic, *args, dp=dp))

    def dist(p: dict) -> float:
        return sum(abs(math.log2(p[k]) - math.log2(max(tile_analytic[k], 1)))
                   for k in keys)

    def order_key(plan: dict):
        return tuple(sorted((k, str(v)) for k, v in plan.items()))

    scored, seen = [], set()
    for vi, var in enumerate(variants):
        for ti, tiles in enumerate(tile_plans):
            plan = {**tiles, **var}
            key = order_key(plan)
            if key in seen:
                continue
            seen.add(key)
            # a variant flip at the analytic tiles is the interesting
            # hypothesis (backend/cutoff/morton) — rank it right behind the
            # analytic plan, ahead of the tile fine-tuning ladder
            score = vi / 10.0 if (vi and ti == 0) else dist(tiles) + vi
            scored.append((score, plan))
    scored.sort(key=lambda t: (t[0], order_key(t[1])))
    rest = [p for _, p in scored if p != analytic]
    return [analytic] + rest[:max(max_candidates - 1, 0)]


# ---------------------------------------------------------------------------
# timing harness
# ---------------------------------------------------------------------------

def measure_us(fn, args, *, iters: int = 5, kwargs: Optional[dict] = None) -> float:
    """Median-of-``iters`` wall time in microseconds, compile excluded (one
    warm-up call runs and blocks before the clock starts)."""
    kwargs = kwargs or {}
    jax.block_until_ready(fn(*args, **kwargs))
    samples = []
    for _ in range(max(iters, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kwargs))
        samples.append((time.perf_counter() - t0) * 1e6)
    return statistics.median(samples)


def _concrete(args) -> bool:
    return not any(isinstance(a, jax.core.Tracer) for a in args)


def search(op: str, *args, iters: int = 5, max_candidates: int = 16,
           save: bool = True, **kwargs) -> dict:
    """Time the candidate ladder for one op/shape on the real kernel path
    (native where supported, interpret elsewhere), record the winner in the
    device table, and return the table entry."""
    from repro.kernels import registry

    spec = registry.get(op)
    if not _concrete(args):
        raise TypeError(f"autotune.search({op!r}) needs concrete arrays, "
                        "not tracers")
    dp = planner.device_params()
    interpret = planner.resolve_interpret(None, spec.supported())
    variant = {k: v for k, v in kwargs.items() if k in variant_keys(op)}
    cands = candidates(op, *args, dp=dp, max_candidates=max_candidates,
                       variant=variant)
    timed = []
    for plan in cands:
        try:
            us = measure_us(spec.pallas, args, iters=iters,
                            kwargs={**kwargs, "interpret": interpret, **plan})
        except Exception as exc:
            # the envelope filter allows working sets up to the full queried
            # fast memory (the wins live beyond the planner's 1/3 slack), so
            # a near-limit candidate may fail native compilation — skip it,
            # never abort the sweep
            log.warning("autotune %s: candidate %s failed (%s); skipping",
                        op, plan, exc)
            continue
        timed.append((us, plan))
    if not timed:
        raise RuntimeError(f"autotune {op}: every candidate failed to run")
    best_us, best = min(timed, key=lambda t: t[0])
    analytic = cands[0]
    analytic_us = next((us for us, p in timed if p == analytic), None)
    entry = {
        "plan": best,
        "us": round(best_us, 1),
        "analytic_plan": analytic,
        "analytic_us": None if analytic_us is None else round(analytic_us, 1),
        "iters": iters,
        "candidates": len(cands),
    }
    table = load_table(dp.kind)
    table[entry_key(op, *args, kwargs=kwargs)] = entry
    if save:
        save_table(dp.kind)
    return entry


# ---------------------------------------------------------------------------
# dispatch-time overlay (the integration point for registry.dispatch)
# ---------------------------------------------------------------------------

def lookup(op: str, *args, kwargs: Optional[dict] = None) -> Optional[dict]:
    """The persisted tuned plan for this op/shape-class/dtype/flags, or
    None.  ``kwargs`` are the call's semantic kwargs (they key the masking
    regime — see :func:`sem_class`)."""
    entry = load_table().get(entry_key(op, *args, kwargs=kwargs))
    return dict(entry["plan"]) if entry else None


# (tune_dir, wanted key, borrowed key) triples already logged — interpolation
# fires on every dispatch trace of a cold shape, so log once, not per trace
_INTERP_LOGGED: set[tuple] = set()


def _shape_distance(a: str, b: str) -> Optional[float]:
    """Log2 distance between two ``shape_class`` strings; None when the
    array structures differ (different arity or rank — not comparable)."""
    pa, pb = a.split("_"), b.split("_")
    if len(pa) != len(pb):
        return None
    total = 0.0
    for xa, xb in zip(pa, pb):
        da, db = xa.split("x"), xb.split("x")
        if len(da) != len(db):
            return None
        for u, v in zip(da, db):
            if u == "scalar" or v == "scalar":
                if u != v:
                    return None
                continue
            total += abs(math.log2(int(u)) - math.log2(int(v)))
    return total


def nearest_plan(op: str, *args, kwargs: Optional[dict] = None) -> Optional[dict]:
    """Cross-shape interpolation: on an exact-key miss, borrow the tuned
    plan from the *nearest* recorded shape_class with the same
    ``(op, dtype, semantic flags)`` — a neighbouring shape's measured
    constants beat the cold analytic plan.  Logs once per borrowed key."""
    table = load_table()
    if not table:
        return None
    want = entry_key(op, *args, kwargs=kwargs)
    wop, wshape, wrest = want.split("|", 2)
    best = None
    for key, entry in table.items():
        try:
            kop, kshape, krest = key.split("|", 2)
        except ValueError:
            continue
        if kop != wop or krest != wrest or kshape == wshape:
            continue
        d = _shape_distance(wshape, kshape)
        if d is None:
            continue
        if best is None or (d, key) < (best[0], best[1]):
            best = (d, key, entry)
    if best is None:
        return None
    _, key, entry = best
    tag = (str(tune_dir()), want, key)
    if tag not in _INTERP_LOGGED:
        _INTERP_LOGGED.add(tag)
        log.info("autotune: no tuned entry for %s; interpolating from "
                 "nearest recorded class %s", want, key)
    return dict(entry["plan"])


def overlay(op: str, args, *, search_kwargs: Optional[dict] = None) -> dict:
    """Tuned tile kwargs to merge over the analytic plan (empty dict when
    the mode is off, the op is untunable, or the cache is cold).  In
    ``search`` mode a miss on concrete arrays triggers an in-line search;
    otherwise a miss falls back to cross-shape interpolation
    (:func:`nearest_plan`) before going cold."""
    m = mode()
    if m == "off" or op not in _TUNE:
        return {}
    plan = lookup(op, *args, kwargs=search_kwargs)
    if plan is None and m == "search" and _concrete(args):
        plan = dict(search(op, *args, **(search_kwargs or {}))["plan"])
    if plan is None:
        plan = nearest_plan(op, *args, kwargs=search_kwargs)
    if plan is None:
        return {}
    return snap_plan(op, args, plan)
