"""Resource-oblivious kernel substrate.

The paper's claim — sequential-level cache and block costs *without knowing
M or B* — carried from the simulator into the Pallas layer.  Four policy
points, each in exactly one module:

``policy``
    The ambient :class:`~repro.kernels.policy.ExecutionPolicy`: ONE place
    where every backend/variant/autotune decision lives, the way the
    paper's scheduler keeps *where a task runs* out of the computation dag.
    A frozen value object (per-op ``impl`` map with a ``"*"`` wildcard,
    per-op ``variants``, ``autotune`` mode, ``interpret``,
    ``strict_tiles``) on a context stack: the base is assembled from the
    environment (``REPRO_IMPL`` with the
    ``op=backend[:knob=value]*[,op=backend...]`` grammar — ``:knob=value``
    suffixes set per-op variant knobs, e.g.
    ``attention=pallas:kv_dtype=int8`` for the quantized KV cache or
    ``matmul=pallas:qkv_fused=true`` for fused QKV projections —
    ``REPRO_STRICT_TILES``, ``REPRO_INTERPRET``), launchers
    ``install()`` the ``--impl`` flag as a process layer, and
    ``apply()``/``pin()`` push scoped overrides (a pin records its
    reason).  An ``interpret`` variant knob
    (``--impl 'op=pallas:interpret=true'``) forces interpret mode per op,
    sitting between the explicit call arg and the policy-global flag.
    Model code never names a backend; the deprecated
    ``RunOptions.attention_impl``/``matmul_impl`` knobs survive only as a
    compat shim that constructs an equivalent scope.

``registry``
    ``resolve(name, **context)`` is the single backend-resolution code path
    (it replaced ``resolve_matmul_impl``, the attention impl branch, and
    ``default_impl``): policy lookup, ``auto`` expansion via
    ``supported()``, then the capability gates — ``has_vjp`` (ops without a
    registered backward never serve possibly-differentiated model callers)
    and the per-op ``needs`` predicate (shape/dtype context the kernel
    cannot take, e.g. a custom softmax scale).  ``dispatch(name, *args,
    **kw)`` is the only way model / launch / benchmark code invokes a
    kernel: the oracle for a jnp resolution, else the Pallas kernel with
    planner tiles + autotune overlay + the policy's variant overrides +
    explicit call-site kwargs (strongest last); ``impl=`` on the call is
    the per-call escape hatch for experiments.  Each op (``scan``,
    ``matmul``, ``transpose``, ``attention``, ``fft``) registers a
    ``KernelSpec``; the ``attention`` kernel covers cached decode via
    ``q_offset``/``kv_len`` and registers a recomputation backward, so
    serving prefill/decode and training all dispatch through one path.
    Both decode operands also take per-row ``(rows,)`` vectors — ``rows``
    dividing the folded batch*heads axis, each row's scalar fanning out
    over its ``bh // rows`` folded heads (the batch-major fold) — read
    per-lane from SMEM, so one launch serves a continuous batch whose
    slots sit at different cache depths: concrete vectors keep the
    static grid shrink (to the max length), traced vectors keep the
    no-recompile property across ragged batch compositions
    (``launch.engine`` is the consumer).  A ``kv_len == 0`` lane attends
    nothing and emits exact zeros (the parked-row contract).  The caller
    side of that contract lives in ``repro.models.cache``: the
    ``DecodeCache`` layouts — ``LinearKV`` (dense slabs + int8 scales,
    per-row ``pos``), ``RingKV`` (a wrapped window buffer whose
    ``attend_lens``/``slot_positions`` map raw slots onto the kernel's
    per-row ``q_offset``/``kv_len`` vectors, sound because causal softmax
    is permutation-invariant over the live window), ``CrossKV`` (frozen
    after the first chunk) and ``StateCarry`` (recurrent conv/LRU/SSD
    state with a per-row validity mask) — are the single source of truth
    for per-row cache state across every model family.
    GQA is kernel-native: callers hand K/V over at their *native* head
    count with ``n_heads`` declaring the query head count, and the kv
    ``index_map`` routes every query head's grid steps into its group's KV
    row (dk/dv group-sum in the transposed grid's scratch) — no caller ever
    materializes a cache-sized ``repeat_kv``.  An int8 KV cache
    (``k_scale``/``v_scale`` per (batch, kv-head), selected by the policy's
    attention ``kv_dtype=int8`` variant) dequantizes inside the kernel's
    block load, streaming the cache at a quarter of the f32 bytes.
    ``simulator_program(name, n)`` builds the op's access-trace HBP program
    (``core.algorithms``) under the same name, so kernel dispatch and
    simulator cost cross-checks share one op namespace.

``planner``
    Derives every tile shape at trace time from *queried* device parameters
    (fast-memory bytes, lane/sublane tiling, dtype width) pushed through the
    ``repro.core.costmodel`` envelopes (``oblivious_tile_edge``,
    ``seq_cache_complexity_*``).  No kernel signature carries a hard-coded
    block size; ``plan_*`` functions return divisor-exact tile dicts
    (``plan_attention`` budgets per KV dtype — an int8 cache stream earns a
    proportionally deeper KV panel) and
    ``resolve_run_options`` fills the model layer's ``RunOptions`` tiles.
    ``REPRO_FAST_BYTES`` overrides the queried fast-memory size.

``morton``
    The §3.2 bit-interleaved (BI) codec on plain integer arithmetic (works
    on traced grid indices), and ``grid_decode(nm, nn)`` — the shared grid
    scheduler giving Morton order on square power-of-two tile grids with a
    row-major fallback.  Used by ``hbp_matmul``, ``bi_transpose``, and
    ``flash_attention``; cross-validated against ``repro.core.layouts``.

Backend selection
-----------------
``matmul`` is a multi-backend op: "classical" by default, "strassen" (the
paper's Type-2 Depth-n-MM exemplar, W = n^2.807) only on request — the
``backend="strassen"`` kwarg or the policy variant
``matmul=pallas:backend=strassen`` — because its quadrant sums add row i
to row i + m/2: where rows are tokens of different requests, one
request's numbers would depend on what shares its launch.  A request is
gated by ``planner.plan_matmul_backend``, which compares the costmodel
envelopes (``seq_cache_complexity_strassen`` vs the classical Q) at the
queried device params: square, pow2-friendly, fp32/bf16 shapes above the
modeled crossover edge (~sqrt M) recurse down to a ``cutoff`` beneath which
``strassen_matmul``'s 7-product quadrant schedule leaves dispatch to the
Morton-ordered ``hbp_matmul`` tile kernel with f32 accumulation preserved
through the combination tree.  The registry's ``matmul`` entry
(``strassen_matmul.matmul``) runs the variant at dispatch and registers a
custom VJP (dA = g Bᵀ, dB = Aᵀ g, each a classical product), so model
matmuls (``models.common``'s
``project``/``gated_mlp``/``logits_matmul``/``expert_project`` — MLPs, QKV
and output projections, logits, MoE expert slabs) route through the
kernels under training and serving alike whenever the ambient policy says
so.  A requested variant (policy ``variants`` or call-site kwarg) keys the
autotune replay lookup, so a classical run never replays tiles tuned for
the Strassen entry.

Tuning
------
``autotune`` closes the measure→persist→replay loop over the planner: the
analytic plans stay the source of truth, but measured winners (searched on a
power-of-two ladder around the analytic point, filtered by the costmodel
envelope and each kernel's divisibility constraints) are persisted per
``(device_kind, op, shape_class, dtype, semantic flags)`` as JSON under
``REPRO_TUNE_DIR`` (default ``~/.cache/repro/autotune``) and overlaid at
dispatch time.  The mode resolves through ``autotune.mode()``: an
``autotune`` field set on the ambient policy (a scope or the RunOptions
shim) wins, then the launcher's ``startup``/``set_mode`` pin, then
``REPRO_AUTOTUNE``, else ``off``:

  * ``off``    — analytic plans only; the default for bare dispatch so
    benchmarks and tests see the pure planner unless they opt in;
  * ``replay`` — overlay persisted measurements; a cold cache is a no-op;
    the launchers' startup default;
  * ``search`` — replay, plus a table miss on concrete (non-traced) arrays
    triggers an in-line timed search whose winner is persisted.

``benchmarks/autotune.py`` populates tables across a shape sweep;
``benchmarks/bench_kernels.py`` reports the resulting ``pallas_tuned_us``
next to the fixed/planned arms.  Kernel signatures stay oblivious: tuning
never adds a knob to a kernel, it only picks values for the existing ones.

Kernel modules (``bp_scan``, ``hbp_matmul``, ``strassen_matmul``,
``bi_transpose``, ``flash_attention``, ``bi_fft``) stay importable directly
for tests and experiments; ``ref`` holds the pure-jnp oracles.

Layers above: ``repro.models`` calls kernels only through ``dispatch``;
``repro.launch`` stacks the serving tiers on top of the models — lockstep
``serve.Server``, continuous-batching ``engine.Engine``, and the
multi-replica ``router.Router`` fleet, whose replicas each carry this
layer's policy ``describe()`` and autotune ``provenance()`` as their
per-replica provenance rows (replicas on different device kinds replay
different tuned tables; the router surfaces which).
"""
from repro.kernels import autotune, morton, planner, policy, ref, registry
from repro.kernels.bi_fft import bi_fft
from repro.kernels.bi_transpose import bi_transpose
from repro.kernels.bp_scan import bp_scan
from repro.kernels.flash_attention import flash_attention
from repro.kernels.hbp_matmul import hbp_matmul
from repro.kernels.policy import ExecutionPolicy
from repro.kernels.registry import dispatch, resolve
from repro.kernels.strassen_matmul import strassen_matmul

__all__ = [
    "autotune",
    "morton",
    "planner",
    "policy",
    "ref",
    "registry",
    "ExecutionPolicy",
    "dispatch",
    "resolve",
    "bp_scan",
    "bi_transpose",
    "bi_fft",
    "flash_attention",
    "hbp_matmul",
    "strassen_matmul",
]
