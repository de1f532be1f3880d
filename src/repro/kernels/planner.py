"""Resource-oblivious tile planner for the Pallas kernel substrate.

The paper's HBP algorithms never see M or B; the scheduler gets sequential-
level cache costs anyway.  The kernel-layer translation: no kernel signature
carries a hard-coded tile size.  Block shapes are *derived* at trace time
from queried device parameters (fast-memory bytes, lane/sublane tiling,
dtype width) pushed through the ``repro.core.costmodel`` envelopes —
``oblivious_tile_edge`` gives the O(sqrt M) square-tile bound, and the
``seq_cache_complexity_*`` functions bound the modeled traffic of the chosen
plan.  Explicit override kwargs on ``registry.dispatch`` are preserved for
experiments.

Every plan function returns a dict of the kernel's tile kwargs, with each
tile an exact divisor of its dimension (the kernels assert divisibility) and
a multiple of the hardware (sublane, lane) tiling whenever the shape allows.
"""
from __future__ import annotations

import dataclasses
import math
import os
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import costmodel

# TPU vector-memory lane width (last-dim tiling) in elements.
LANE = 128

# Fallback fast-memory sizes for hosts: CPU a shared L2+L3 slice — 8 MiB also
# reproduces the seed's hand-tuned 512/1024 attention blocks exactly, so the
# planner's CPU defaults are behavior-preserving.  GPU: an L2-ish slice.
_DEFAULT_FAST_BYTES = {"cpu": 8 * 2**20, "gpu": 16 * 2**20}

# TPU fast memory, keyed by ``device_kind``: the scoped-VMEM limit Mosaic
# grants a kernel by default (XLA's ``--xla_tpu_scoped_vmem_limit_kib``:
# 16 MiB through v5p, 32 MiB on v6e).  Physical VMEM is larger on some
# chips (v5e: 128 MiB per core, ``jax/_src/pallas/mosaic/tpu_info.py``),
# but a kernel that plans past the scoped limit is refused at compile time.
# ``memory_stats()`` reports HBM, never VMEM, so it cannot stand in.
TPU_VMEM_BYTES = {
    "TPU v4": 16 * 2**20,
    "TPU v5 lite": 16 * 2**20,
    "TPU v5e": 16 * 2**20,
    "TPU v5": 16 * 2**20,
    "TPU v5p": 16 * 2**20,
    "TPU v6 lite": 32 * 2**20,
    "TPU v6e": 32 * 2**20,
}

# Block-transfer granularity B (bytes): HBM burst on TPU, cache line on CPU.
_DEFAULT_LINE_BYTES = {"tpu": 512, "cpu": 64, "gpu": 128}


@dataclass(frozen=True)
class DeviceParams:
    """The queried machine parameters the planner is oblivious *about* —
    it reads them at trace time instead of baking them into signatures."""

    platform: str
    kind: str
    fast_bytes: int  # M: fast-memory capacity the tiles must fit in
    line_bytes: int  # B: block-transfer granularity
    lane: int = LANE

    def sublane(self, dtype) -> int:
        """Second-minor tiling multiple: 8 f32 rows, packed 2x/4x for
        narrower dtypes (TPU (8, 128) native tile with sublane packing)."""
        itemsize = jnp.dtype(dtype).itemsize
        return max(32 // max(itemsize, 1), 8)


def native() -> bool:
    """True where Pallas kernels compile natively: a TPU backend."""
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: Optional[bool],
                      supported: Optional[bool] = None) -> bool:
    """The kernels' ``interpret`` default, resolved here only: ``None``
    means native where the kernel compiles (``supported``; default: on a
    TPU) and the Pallas interpreter everywhere else."""
    if interpret is not None:
        return bool(interpret)
    return not (native() if supported is None else supported)


# device_params is memoized: every dispatch trace asks for it, and
# ``jax.devices()`` is not free.  The REPRO_FAST_BYTES value participates in
# the cache key so flipping the env var takes effect without a clear, but
# tests that monkeypatch deeper (fake devices, backend swaps) should call
# ``clear_device_params_cache()``.
_DP_CACHE: dict = {}


def device_params(device=None) -> DeviceParams:
    """Query the current device (memoized).  ``REPRO_FAST_BYTES`` overrides
    the fast-memory size (useful to replay a plan for a different machine).
    A TPU reads :data:`TPU_VMEM_BYTES` by its ``device_kind`` and raises on
    a kind the table lacks; other platforms consult
    ``device.memory_stats()`` when the backend exposes it, falling back to
    the per-platform defaults."""
    env = os.environ.get("REPRO_FAST_BYTES")
    cache_key = (device, env)
    try:
        return _DP_CACHE[cache_key]
    except (KeyError, TypeError):  # TypeError: unhashable fake device
        pass
    dev = device if device is not None else jax.devices()[0]
    platform = getattr(dev, "platform", "cpu")
    kind = getattr(dev, "device_kind", platform)
    if env:
        fast = int(env)
    elif platform == "tpu":
        if kind not in TPU_VMEM_BYTES:
            raise ValueError(f"no fast-memory size known for TPU kind "
                             f"{kind!r}; add it to planner.TPU_VMEM_BYTES")
        fast = TPU_VMEM_BYTES[kind]
    else:
        fast = (_queried_fast_bytes(dev, platform)
                or _DEFAULT_FAST_BYTES.get(platform, 8 * 2**20))
    line = _DEFAULT_LINE_BYTES.get(platform, 64)
    dp = DeviceParams(platform=platform, kind=kind, fast_bytes=fast,
                      line_bytes=line)
    try:
        _DP_CACHE[cache_key] = dp
    except TypeError:
        pass
    return dp


def clear_device_params_cache() -> None:
    """Drop memoized device queries (tests that fake devices or change the
    backend under the planner)."""
    _DP_CACHE.clear()


def _queried_fast_bytes(dev, platform: str):
    """Real fast-memory size from ``device.memory_stats()`` when the backend
    reports one.  An explicit fast-memory key wins outright; a ``bytes_limit``
    below the platform default shrinks it (the device genuinely has less),
    while HBM-sized limits are ignored — they are not the M the O(sqrt M)
    tile envelopes need."""
    try:
        stats = dev.memory_stats()
    except Exception:
        return None
    if not isinstance(stats, dict):
        return None
    for key in ("vmem_size_bytes", "fast_memory_bytes"):
        val = stats.get(key)
        if isinstance(val, (int, float)) and val > 0:
            return int(val)
    default = _DEFAULT_FAST_BYTES.get(platform, 8 * 2**20)
    limit = stats.get("bytes_limit")
    if isinstance(limit, (int, float)) and 0 < limit < default:
        return int(limit)
    return None


# ---------------------------------------------------------------------------
# tile arithmetic
# ---------------------------------------------------------------------------

def _pow2_floor(x: int) -> int:
    return 1 << max(int(x).bit_length() - 1, 0)


def _divisors_desc(n: int) -> list[int]:
    small, large = [], []
    i = 1
    while i * i <= n:
        if n % i == 0:
            small.append(i)
            if i != n // i:
                large.append(n // i)
        i += 1
    return large + small[::-1]  # large is built descending (n//i for i asc)


def divisor_tile(dim: int, cap: int, multiple: int = 1) -> int:
    """Largest divisor of ``dim`` that is <= cap, preferring multiples of the
    hardware tiling ``multiple``; falls back to any divisor (odd shapes)."""
    if dim <= 0:
        return 1
    cap = max(1, min(cap, dim))
    divs = _divisors_desc(dim)
    for d in divs:
        if d <= cap and d % multiple == 0:
            return d
    for d in divs:
        if d <= cap:
            return d
    return 1


def _tile(dim: int, cap: int, multiple: int, dp: DeviceParams) -> int:
    """:func:`divisor_tile`, held to the TPU's block rule: a block dimension
    is a multiple of its hardware tiling or the whole axis.  Where no
    aligned divisor fits under ``cap``, a TPU plan takes the whole axis
    (:func:`_fit` then checks it against fast memory) instead of a
    misaligned divisor Mosaic would refuse."""
    t = divisor_tile(dim, cap, multiple)
    if dp.platform == "tpu" and t % multiple and t != dim:
        return dim
    return t


def _budget(dp: DeviceParams) -> int:
    # One third of fast memory: leave headroom for double buffering and the
    # out tile, mirroring the paper's constant-factor slack in Lemma 4.4.
    return max(dp.fast_bytes // 3, 1024)


# ---------------------------------------------------------------------------
# working sets: the resident bytes of one grid step, per op.  Every plan
# below is checked against ``dp.fast_bytes`` with these (``_fit``), and the
# autotune ladder filters its candidates with the same functions.
# ---------------------------------------------------------------------------

def scan_row_block(rows: int) -> int:
    """Rows per scan grid step: 8 (one f32 sublane tile) when the row count
    allows, else every row — either meets the TPU's (8, 128) layout rule."""
    return 8 if rows % 8 == 0 else rows


def scan_working_set(block: int, itemsize: int, rows: int) -> int:
    """in, out, local and offset buffers of one scan leaf over ``rows``
    input rows."""
    return 4 * block * itemsize * scan_row_block(rows)


def matmul_working_set(bm: int, bn: int, bk: int, itemsize: int) -> int:
    """A and B panels in the operand dtype plus the f32 accumulator."""
    return (bm * bk + bk * bn) * itemsize + 4 * bm * bn


def transpose_working_set(bt: int, itemsize: int) -> int:
    """The in tile and the out tile."""
    return 2 * bt * bt * itemsize


def attention_working_set(q_block: int, kv_block: int, hd: int,
                          itemsize: int, kv_itemsize: int) -> int:
    """q rows + f32 acc rows, k/v panels (kv width), the f32 P tile, and the
    (m, l) columns."""
    return (q_block * hd * (itemsize + 4) + 2 * kv_block * hd * kv_itemsize
            + 4 * q_block * kv_block + 8 * q_block)


def _fit(op: str, plan: dict, ws: int, dp: DeviceParams) -> dict:
    """Return ``plan`` if its working set fits the device's fast memory;
    raise when even this (smallest legal) plan does not."""
    if ws > dp.fast_bytes:
        raise ValueError(f"{op} plan {plan} needs {ws} B of fast memory; "
                         f"{dp.kind} has {dp.fast_bytes} B")
    return plan


# ---------------------------------------------------------------------------
# per-op plans
# ---------------------------------------------------------------------------

def plan_scan(shape, dtype, dp: Optional[DeviceParams] = None) -> dict:
    """BP leaf size for the two-pass prefix scan: the largest lane-aligned
    block whose 4 resident buffers (in, out, local, offset) of
    :func:`scan_row_block` rows fit the envelope."""
    dp = dp or device_params()
    n = shape[-1]
    rows = shape[0] if len(shape) > 1 else 1
    itemsize = jnp.dtype(dtype).itemsize
    cap = _pow2_floor(max(_budget(dp) // scan_working_set(1, itemsize, rows),
                          1))
    plan = {"block": _tile(n, cap, dp.lane, dp)}
    return _fit("scan", plan, scan_working_set(plan["block"], itemsize, rows),
                dp)


# dtypes the Strassen schedule may serve: the 18 extra adds per level are
# benign under f32 accumulation (fp32 natively, bf16 with f32 acc); low-
# precision integer/fp8 matmuls lose more to the adds than the 7/8 work
# saving buys, so they stay classical
_STRASSEN_DTYPES = ("float32", "bfloat16")


def strassen_cutoff(dtype, dp: Optional[DeviceParams] = None) -> int:
    """Recursion cutoff for the Strassen-schedule matmul: the largest
    power-of-two edge where the classical envelope still wins at the queried
    device params (``costmodel.strassen_crossover_edge`` over the planner's
    budgeted fast memory, in elements of ``dtype``)."""
    dp = dp or device_params()
    itemsize = jnp.dtype(dtype).itemsize
    m_elems = max(_budget(dp) // itemsize, 2)
    b_elems = max(dp.line_bytes // itemsize, 1)
    return costmodel.strassen_crossover_edge(m_elems, b_elems)


def plan_matmul_backend(m: int, k: int, n: int, dtype,
                        dp: Optional[DeviceParams] = None) -> dict:
    """The schedule a call that asks for Strassen runs, by the costmodel
    envelopes: ``strassen`` (plus its recursion ``cutoff``) when the shape
    is square with pow2-friendly halving down to the modeled crossover edge
    and the dtype tolerates the extra adds (fp32 / bf16-with-f32-acc);
    ``classical`` otherwise.  :func:`plan_matmul` never asks: see there."""
    dp = dp or device_params()
    if not (m == k == n and jnp.dtype(dtype).name in _STRASSEN_DTYPES):
        return {"backend": "classical"}
    cut = strassen_cutoff(dtype, dp)
    levels, edge = 0, n
    while edge > cut and edge % 2 == 0:
        edge //= 2
        levels += 1
    # the recursion must reach the classical-wins regime by halving alone
    # (an odd edge stuck above the cutoff leaves oversized classical leaves)
    if levels == 0 or edge > cut:
        return {"backend": "classical"}
    return {"backend": "strassen", "cutoff": cut}


def plan_matmul(m: int, k: int, n: int, dtype,
                dp: Optional[DeviceParams] = None) -> dict:
    """Square (bm, bn, bk) tiles from the O(sqrt M) envelope: two operand
    tiles in ``dtype`` plus the f32 accumulator must fit the budget.

    The schedule is classical unless the caller asks for Strassen (the
    ``backend="strassen"`` kwarg, or the policy variant
    ``matmul=pallas:backend=strassen``; :func:`plan_matmul_backend` then
    gates it).  Strassen's quadrant sums add row i to row i + m/2, so where
    rows are independent tokens — of different requests, in a serving
    batch — one token's result would depend on what shares its launch."""
    dp = dp or device_params()
    itemsize = jnp.dtype(dtype).itemsize
    # bytes(t) = 2 t^2 itemsize (A, B panels) + 4 t^2 (f32 acc)
    edge = costmodel.oblivious_tile_edge(_budget(dp), 1, 2 * itemsize + 4)
    t = _pow2_floor(edge)
    sub = dp.sublane(dtype)
    plan = {
        "bm": _tile(m, t, sub, dp),
        "bn": _tile(n, t, dp.lane, dp),
        "bk": _tile(k, t, dp.lane, dp),
    }
    return _fit("matmul", plan, matmul_working_set(plan["bm"], plan["bn"],
                                                   plan["bk"], itemsize), dp)


def plan_transpose(m: int, n: int, dtype,
                   dp: Optional[DeviceParams] = None) -> dict:
    """One square tile edge serving both dims (the kernel asserts the tile
    divides each): derived from the 2-buffer (in tile, out tile) envelope."""
    dp = dp or device_params()
    itemsize = jnp.dtype(dtype).itemsize
    t = _pow2_floor(costmodel.oblivious_tile_edge(_budget(dp), 2, itemsize))
    g = math.gcd(m, n) if m != n else m
    plan = {"bt": _tile(g, t, dp.lane, dp)}
    return _fit("transpose", plan, transpose_working_set(plan["bt"], itemsize),
                dp)


# a query block this short (decode / speculative lookahead) flips the plan
# into the decode regime: the whole q fits one block and the budget goes to
# the KV stream
DECODE_MAX_SQ = 16


def plan_attention(sq: int, sk: int, hd: int, dtype,
                   dp: Optional[DeviceParams] = None, *,
                   kv_dtype=None) -> dict:
    """Flash-attention (q_block, kv_block): solve the working-set quadratic
    4 t^2 (the f32 P tile) + t * hd * (itemsize + 2 kv_itemsize + 4)
    <= budget for the square block t, then clamp each block to a divisor of
    its axis.

    Per-dtype envelopes: ``kv_dtype`` (default: the q dtype) sets the k/v
    element width independently — a quantized int8 KV cache streams panels
    at a quarter of the f32 bytes, so the same budget admits a 4x deeper KV
    panel (the SPMS register/block-reuse argument at reduced element width),
    and the sublane multiple for the KV axis follows the KV dtype's packing
    (32 int8 rows vs 8 f32).

    Decode regime (sq <= DECODE_MAX_SQ over a longer KV axis — serving a
    growing cache): the q block is the whole (tiny) query and the envelope
    is spent on the deepest lane-aligned KV panel that fits — per KV row
    the resident bytes are the k/v rows plus the f32 P column."""
    dp = dp or device_params()
    itemsize = jnp.dtype(dtype).itemsize
    kv_item = jnp.dtype(kv_dtype).itemsize if kv_dtype is not None else itemsize
    kv_sub = dp.sublane(kv_dtype if kv_dtype is not None else dtype)
    budget = _budget(dp)
    if sq <= DECODE_MAX_SQ and sk > sq:
        per_row = 2 * hd * kv_item + 4 * sq + 4  # k/v rows + P col + l bits
        kb = _pow2_floor(max(budget // per_row, 1))
        plan = {"q_block": sq, "kv_block": _tile(sk, kb, kv_sub, dp)}
    else:
        # q row + f32 acc row + k/v rows (kv width) + (m, l)
        c1 = hd * (itemsize + 2 * kv_item + 4) + 8
        t = int((-c1 + math.sqrt(c1 * c1 + 16.0 * budget)) / 8.0)
        t = _pow2_floor(max(t, 1))
        sub = dp.sublane(dtype)
        plan = {"q_block": _tile(sq, t, sub, dp),
                # the kv stream gets the deeper panel
                "kv_block": _tile(sk, 2 * t, kv_sub, dp)}
    ws = attention_working_set(plan["q_block"], plan["kv_block"], hd,
                               itemsize, kv_item)
    return _fit("attention", plan, ws, dp)


def plan_fft(n: int, dp: Optional[DeviceParams] = None) -> dict:
    """Four-step split n = n1 * n2 with n1 ~ sqrt(n): both DFT factors stay
    inside the O(sqrt M) envelope, matching the paper's Q = (n/B) log_M n
    recursion depth of one for n <= M^2."""
    if n <= 1 or n & (n - 1) != 0:
        return {"n1": 1}
    return {"n1": 1 << (n.bit_length() - 1) // 2}


# ---------------------------------------------------------------------------
# modeled traffic (the envelope check)
# ---------------------------------------------------------------------------

def modeled_matmul_misses(m: int, k: int, n: int, dtype, plan: dict,
                          dp: Optional[DeviceParams] = None) -> float:
    """Cache-line traffic of the planned tiling; tests assert it lands within
    a constant factor of ``costmodel.seq_cache_complexity_mm``."""
    dp = dp or device_params()
    itemsize = jnp.dtype(dtype).itemsize
    bm, bn, bk = plan["bm"], plan["bn"], plan["bk"]
    steps = (m // bm) * (n // bn) * (k // bk)
    per_step = (bm * bk + bk * bn) * itemsize
    out = m * n * itemsize
    return (steps * per_step + out) / dp.line_bytes


# ---------------------------------------------------------------------------
# RunOptions resolution — the launch/model layers' single policy point
# ---------------------------------------------------------------------------

def default_attention_blocks(dp: Optional[DeviceParams] = None,
                             head_dim: int = 128,
                             dtype=jnp.bfloat16) -> tuple[int, int]:
    """Shape-agnostic blockwise-attention leaf sizes for the jnp (XLA) path:
    the same envelope as :func:`plan_attention`, uncommitted to a sequence
    length (the model clamps to the actual sequence at call time)."""
    plan = plan_attention(1 << 30, 1 << 30, head_dim, dtype, dp)
    return plan["q_block"], plan["kv_block"]


def resolve_run_options(opts, *, head_dim: int = 128, dtype=jnp.bfloat16):
    """Fill planner-owned ``None`` fields of a ``RunOptions``-like frozen
    dataclass (q_block, kv_block, autotune) from the queried device and the
    model's actual head_dim / activation dtype.  Idempotent."""
    updates = {}
    if opts.q_block is None or opts.kv_block is None:
        qb, kb = default_attention_blocks(head_dim=head_dim, dtype=dtype)
        if opts.q_block is None:
            updates["q_block"] = qb
        if opts.kv_block is None:
            updates["kv_block"] = kb
    if getattr(opts, "autotune", "off") is None:
        from repro.kernels import autotune  # layered above the planner

        updates["autotune"] = autotune.resolve_mode()
    if not updates:
        return opts
    return dataclasses.replace(opts, **updates)
