"""Kernel registry: one policy-driven dispatch path for every op.

Each op registers a ``KernelSpec`` — a Pallas implementation, the pure-jnp
``ref.py`` oracle, a planner hook that derives tile kwargs from the queried
device (``repro.kernels.planner``), a backend predicate saying when the
Pallas path compiles natively, and capability metadata (``has_vjp``, the
``needs`` shape/dtype gate).  Two entry points consume it:

``resolve(name, policy=None, **context)``
    The single backend-resolution code path (it replaced the per-op
    resolvers: ``resolve_matmul_impl``, the attention impl branch, and
    ``default_impl``).  Looks the op up in the ambient
    :class:`~repro.kernels.policy.ExecutionPolicy` (``"*"`` wildcard,
    default ``"auto"``), expands ``auto`` via ``supported()``, and
    downgrades a Pallas choice to ``jnp`` when capability metadata says the
    kernel cannot serve the call — no registered backward under a possibly
    differentiated caller, or a failing ``needs(**context)`` predicate.

``dispatch(name, *args, impl=None, interpret=None, **kwargs)``
    Invokes the resolved backend: the oracle for ``jnp``/``ref``, else the
    Pallas kernel with planner-derived tiles, overlaid by any persisted
    autotune measurement (``repro.kernels.autotune``), under the policy's
    per-op variant overrides, under explicit call-site tile kwargs.  The
    ``impl`` kwarg is the per-call escape hatch (benchmark arms, oracle
    comparisons); everything else reads the policy.  Dispatch applies the
    ``needs`` capability gate to policy-sourced resolutions, but it cannot
    know whether the caller will differentiate — callers that might (the
    model layer) must pre-resolve through :func:`resolve`, whose
    ``has_vjp`` gate covers autodiff.

Registered ops: ``scan``, ``matmul``, ``transpose``, ``attention``, ``fft``
— the paper's trio of scans / matrix computations / FFT plus the BP
online-softmax reduce.  The same names also key the *simulator* side:
``simulator_program(name, n)`` builds the op's access-trace HBP program
from ``repro.core.algorithms``, so kernel dispatch and simulator cost
cross-checks share one op namespace.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass
from typing import Callable, Optional, Tuple

from repro.kernels import planner, policy, ref
from repro.kernels.bi_fft import bi_fft
from repro.kernels.bi_transpose import bi_transpose
from repro.kernels.bp_scan import bp_scan
from repro.kernels.flash_attention import flash_attention
from repro.kernels.strassen_matmul import matmul as backend_matmul


@dataclass(frozen=True)
class KernelSpec:
    """One registered op.

    ``plan(*arrays, dp=None) -> dict`` produces the tile kwargs for the
    Pallas path, planned for ``dp`` (default: the queried device);
    ``pallas_only`` names the kwargs (tiles + schedule flags) that must be
    stripped before calling the oracle, which takes semantic kwargs only.
    ``supported() -> bool`` says whether the Pallas path compiles natively
    on the current backend (it always *runs* via interpret mode).
    ``has_vjp`` marks ops whose Pallas implementation registers a custom
    backward (safe under autodiff) — :func:`resolve` downgrades the others
    to the jnp path for model callers, which cannot tell a forward-only
    call from a traced-for-grad one.  ``needs(**context) -> bool`` is the
    shape/dtype capability gate: call-site context the kernel cannot serve
    (e.g. attention with a custom softmax scale or a traced window) also
    resolves to jnp.  ``simulator(n, mem, **kw)`` builds the op's
    access-trace twin from ``repro.core.algorithms`` (None = no simulator
    program for this op)."""

    name: str
    pallas: Callable
    ref: Callable
    plan: Callable
    pallas_only: Tuple[str, ...] = ()
    supported: Callable[[], bool] = planner.native
    has_vjp: bool = False
    needs: Optional[Callable[..., bool]] = None
    simulator: Optional[Callable] = None


_REGISTRY: dict[str, KernelSpec] = {}


def register(spec: KernelSpec) -> KernelSpec:
    if spec.name in _REGISTRY:
        raise ValueError(f"kernel {spec.name!r} already registered")
    _REGISTRY[spec.name] = spec
    return spec


def get(name: str) -> KernelSpec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown kernel {name!r}; registered: {names()}") from None


def names() -> list[str]:
    return sorted(_REGISTRY)


def resolve(name: str, pol: Optional[policy.ExecutionPolicy] = None,
            *, differentiable: bool = True, **context) -> str:
    """Resolve the op's backend under the (ambient) policy: ``"pallas"`` or
    ``"jnp"``.  ``auto`` asks ``supported()``; a forced/auto ``pallas``
    downgrades to ``jnp`` when the kernel lacks a registered backward
    (``differentiable`` callers — the model-layer default) or its ``needs``
    predicate rejects the call context.  ``ref`` resolves like ``jnp``:
    both mean "not the Pallas kernel" at this layer."""
    spec = get(name)
    if pol is None:
        pol = policy.current()
    choice = pol.impl_for(name)
    if choice == "auto":
        choice = "pallas" if spec.supported() else "jnp"
    elif choice == "ref":
        choice = "jnp"
    if choice == "pallas":
        if differentiable and not spec.has_vjp:
            choice = "jnp"
        elif spec.needs is not None and not spec.needs(**context):
            choice = "jnp"
    return choice


# ops already warned about dropped overrides (warn once per op, not per
# trace); reset_warnings() clears it between tests
_WARNED_DROPPED: set[str] = set()


def reset_warnings() -> None:
    """Test hook: clear the registry's and autotune's warn/log-once state so
    one test's first-warning does not swallow the next test's."""
    _WARNED_DROPPED.clear()
    from repro.kernels import autotune

    autotune._INTERP_LOGGED.clear()


def _check_dropped_overrides(name: str, overrides: dict, *, strict: bool) -> None:
    """The oracle takes semantic kwargs only, so explicit tile overrides on
    the ref path never reach a kernel.  Silence here means an experiment can
    read 'fixed-tile' numbers that actually ran the un-tiled oracle — warn
    once per op, or raise outright under ``REPRO_STRICT_TILES`` / a
    ``strict_tiles`` policy."""
    dropped = sorted(k for k, v in overrides.items() if v is not None)
    if not dropped:
        return
    msg = (f"dispatch({name!r}): tile override(s) {dropped} ignored on the "
           "ref path (the oracle takes semantic kwargs only); force "
           "impl='pallas' to exercise the tiles")
    if strict:
        raise ValueError(msg)
    if name not in _WARNED_DROPPED:
        _WARNED_DROPPED.add(name)
        warnings.warn(msg, stacklevel=3)


def dispatch(name: str, *args, impl: Optional[str] = None,
             interpret: Optional[bool] = None, **kwargs):
    """Generic dispatch under the ambient execution policy.  ``impl`` is
    the per-call override (``"auto"`` | ``"jnp"``/``"ref"`` | ``"pallas"``);
    None reads the policy's per-op map.  The oracle serves ``jnp``/``ref``;
    ``pallas`` runs the kernel with planner tiles overlaid by autotune
    measurements, the policy's per-op variant overrides, and explicit tile
    kwargs (strongest last)."""
    spec = get(name)
    pol = policy.current()
    native = spec.supported()
    forced = impl is not None and impl != "auto"
    if impl is None:
        impl = pol.impl_for(name)
    if impl == "auto":
        impl = "pallas" if native else "ref"
    # an unforced pallas (policy-sourced, or an explicit impl="auto") still
    # honors the op's capability gate: call context the kernel cannot take
    # (the ``needs`` predicate over the semantic kwargs) falls back to the
    # oracle rather than erroring inside the kernel.  An explicit
    # impl="pallas" skips this — the per-call escape hatch means "I know
    # what the kernel takes"
    if (not forced and impl == "pallas" and spec.needs is not None
            and not spec.needs(**kwargs)):
        if pol.strict_tiles:
            raise ValueError(f"dispatch({name!r}): the kernel cannot take "
                             "this call's context; strict tiles forbid the "
                             "oracle fallback")
        impl = "ref"
    explicit = {k: kwargs.pop(k) for k in list(kwargs) if k in spec.pallas_only}
    explicit = {k: v for k, v in explicit.items() if v is not None}
    pol_variants = {k: v for k, v in pol.variant_for(name).items()
                    if k in spec.pallas_only}
    if impl in ("ref", "jnp"):
        # policy-scoped variants are overrides too: dropping them silently
        # would let a 'forced-variant' experiment read oracle numbers
        _check_dropped_overrides(name, {**pol_variants, **explicit},
                                 strict=pol.strict_tiles)
        return spec.ref(*args, **kwargs)
    overrides = dict(pol_variants)
    overrides.update(explicit)
    tiles = dict(spec.plan(*args))
    from repro.kernels import autotune  # the measured layer above dispatch

    # forced variant knobs (e.g. matmul backend) select which table entry to
    # replay — key the lookup on them alongside the semantic kwargs; tile
    # overrides stay out (they win over the overlay below regardless)
    variant = {k: v for k, v in overrides.items()
               if k in autotune.variant_keys(name)}
    tiles.update(autotune.overlay(name, args,
                                  search_kwargs={**kwargs, **variant}))
    tiles.update(overrides)
    if interpret is None:
        # per-op variant knob (--impl 'op=pallas:interpret=true') sits
        # between the explicit call arg and the policy-global flag
        interpret = pol.variant_for(name).get("interpret")
    if interpret is None:
        interpret = pol.interpret
    return spec.pallas(*args,
                       interpret=planner.resolve_interpret(interpret, native),
                       **kwargs, **tiles)


# ---------------------------------------------------------------------------
# simulator namespace (ROADMAP: one op namespace for kernels + simulator)
# ---------------------------------------------------------------------------

def simulator_program(name: str, n: int, mem=None, **kwargs):
    """Build the op's access-trace HBP program (``repro.core.algorithms``)
    under the same name the kernel dispatches as, so simulator cost
    cross-checks and ``KernelSpec`` lookups share one namespace.  ``n`` is
    the op's natural size (matrix edge for matmul/transpose, length for
    scan/fft); allocates a fresh ``core.hbp.Memory`` unless given one.
    Returns whatever the core builder returns (a program, or a program list
    for multi-pass ops like the two-pass prefix scan)."""
    spec = get(name)
    if spec.simulator is None:
        raise KeyError(f"kernel {name!r} has no registered simulator program; "
                       f"ops with one: "
                       f"{[s for s in names() if get(s).simulator is not None]}")
    if mem is None:
        from repro.core.hbp import Memory

        mem = Memory()
    return spec.simulator(n, mem, **kwargs)


def _sim_scan(n, mem, **kw):
    from repro.core import algorithms

    return algorithms.prefix_sums_programs(n, mem, **kw)


def _sim_matmul(n, mem, **kw):
    from repro.core import algorithms

    return algorithms.strassen_program(n, mem, **kw)


def _sim_transpose(n, mem, **kw):
    from repro.core import algorithms

    return algorithms.MTBI(n, mem, **kw)


def _sim_fft(n, mem, **kw):
    from repro.core import algorithms

    return algorithms.fft_program(n, mem, **kw)


# ---------------------------------------------------------------------------
# registrations
# ---------------------------------------------------------------------------

register(KernelSpec(
    name="scan",
    pallas=bp_scan,
    ref=ref.bp_scan_ref,
    plan=lambda x, dp=None: planner.plan_scan(x.shape, x.dtype, dp),
    pallas_only=("block",),
    simulator=_sim_scan,
))

register(KernelSpec(
    name="matmul",
    # the variant entry point: resolves the plan's backend field
    # ("classical" -> hbp_matmul, "strassen" -> the quadrant recursion) and
    # carries a custom VJP (dA = g B^T, dB = A^T g through the same kernels)
    pallas=backend_matmul,
    ref=ref.matmul_ref,
    plan=lambda a, b, dp=None: planner.plan_matmul(
        a.shape[0], a.shape[1], b.shape[1], a.dtype, dp),
    pallas_only=("bm", "bn", "bk", "morton", "backend", "cutoff"),
    has_vjp=True,
    simulator=_sim_matmul,
))

register(KernelSpec(
    name="transpose",
    pallas=bi_transpose,
    ref=ref.transpose_ref,
    plan=lambda x, dp=None: planner.plan_transpose(x.shape[0], x.shape[1],
                                                   x.dtype, dp),
    pallas_only=("bt", "morton"),
    simulator=_sim_transpose,
))

register(KernelSpec(
    name="attention",
    pallas=flash_attention,
    ref=ref.flash_attention_ref,
    # per-dtype envelopes: an int8 KV cache budgets a deeper panel
    plan=lambda q, k, v, dp=None: planner.plan_attention(
        q.shape[1], k.shape[1], q.shape[2], q.dtype, dp, kv_dtype=k.dtype),
    pallas_only=("q_block", "kv_block"),
    # recomputation-style backward kernels (dq + dk/dv) registered as a
    # custom VJP in flash_attention — training no longer routes around it
    has_vjp=True,
    # the kernel hard-codes the 1/sqrt(hd) scale, and its causal/window
    # kwargs are static — a custom softmax scale or a traced (scan-carried)
    # per-layer window cannot take the kernel route
    needs=lambda softmax_scale=None, window=None, **_: (
        softmax_scale is None and isinstance(window, (int, type(None)))),
))

register(KernelSpec(
    name="fft",
    pallas=bi_fft,
    ref=ref.fft_ref,
    plan=lambda x, dp=None: planner.plan_fft(x.shape[-1], dp),
    pallas_only=("n1",),
    simulator=_sim_fft,
))
