"""Encoding-aware kernel dispatch policy (DESIGN.md §5).

The query engine's hot primitives — ``unpack`` (sub-byte extraction),
``bucketize`` (binary search, the core of every §4 range algorithm),
``segment_sum`` (group-by scatter-reduce) and ``topk`` — each have a
Pallas TPU kernel in this package and a pure-XLA formulation. This module
is the single place that decides, AT TRACE TIME, which implementation a
call site gets, so the decision composes with ``jax.jit`` (the routing is
host-side Python over static shapes; no retracing beyond the usual shape
keys).

Policy resolution, in order:

  1. an explicit ``overrides(...)`` / ``set_policy(...)`` (tests, benches),
  2. environment variables at import (``REPRO_USE_PALLAS`` = ``1``/``0``/
     ``auto``, ``REPRO_SORT_FREE``, ``REPRO_SORT_FREE_MAX_DOMAIN``,
     ``REPRO_BUCKETIZE_MIN_QUERIES``,
     ``REPRO_SEGSUM_MAX_GROUPS``, ``REPRO_PACK``, ``REPRO_PACK_MAX_BITS``,
     ``REPRO_UNPACK_MIN_VALS``, ``REPRO_PREFETCH_DEPTH``,
     ``REPRO_SERVE_BUDGET_BYTES``, ``REPRO_PLAN_CACHE_SIZE``,
     ``REPRO_SERVE_MAX_BATCH``, ``REPRO_TRACE``, ``REPRO_TRACE_BUFFER``,
     ``REPRO_FAULTS``, ``REPRO_TRANSFER_RETRIES``,
     ``REPRO_TRANSFER_BACKOFF_MS`` — docs/KNOBS.md is the canonical
     table),
  3. defaults: Pallas on TPU backends only (interpret mode elsewhere is a
     correctness harness, not a fast path), size thresholds below which
     the fused XLA op wins regardless of backend.

Small-table segment reductions (``segment_sum`` over integers,
``segment_min``, ``segment_max``) route on the table size and the
backend: on the TPU, up to ``G_DENSE`` slots, they compare every row with
every slot and reduce, where XLA's scatter would index the table row by
row.

The sort-free grouping knobs live here too (``enable_sort_free``,
``sort_free_max_domain``): scatter-grouping over a bounded code domain is
the same class of decision — pick the implementation the encoding
metadata proves safe and the size model says is profitable.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.bucketize import B_TILE, bucketize_count_kernel
from repro.kernels.segment_reduce import segment_sum_kernel
from repro.kernels.topk import MAX_KERNEL_K, topk_kernel
from repro.kernels.unpack import unpack_kernel
from repro.kernels import ref as ref_mod

# dtypes the 1-D kernels handle natively (4-byte words; narrower dtypes
# keep the XLA path — their TPU tile shapes differ and the engine only
# ever decodes int32/float32 value tensors on the hot path)
_KERNEL_DTYPES = (jnp.int32, jnp.float32)

MAX_MATMUL_SEGMENTS = 4096  # one-hot matmul: G must fit a VMEM block
# Small-table segment reductions (DESIGN.md §5): up to this many slots, a
# compare against every slot reduced along the rows beats XLA's row-sized
# scatter, which the TPU runs as serialised per-row indexed access (7-9 ms
# per 2^20 rows whatever the table size). The dense form costs about
# 1.1 ms per 1024 slots there, so it wins up to about 6000 slots; 4096 is
# the largest size the on-chip sweep measured (PERF.md §7). A TPU number:
# other backends keep the scatter (``_dense_fuses``).
G_DENSE = 4096
# The counting bucketize compares every query with every boundary, so it
# takes one boundary tile at most; longer lists keep XLA's searchsorted.
COUNT_KERNEL_MAX_BOUNDARIES = B_TILE

# Kernels dispatch never routes to, with the TPU compiler's reason. Each
# rests on a per-lane gather from a VMEM-resident 1-D block (the binary
# search's probe, the run-value fetch, the packed-word fetch), which
# Mosaic refuses; XLA's searchsorted and run-expansion sweep run in their
# place. They stay in the package, reachable through ``ops`` for
# interpret-mode parity; ROADMAP A2 lists them for a rewrite that compiles
# and a measured win on the chip.
_NO_1D_GATHER = "Mosaic: NotImplementedError: Only 2D gather is supported"
OFF_TPU_ROUTE = {
    "bucketize_kernel": _NO_1D_GATHER,
    "rle_decode_kernel": _NO_1D_GATHER,
    "bucketize_packed_kernel": _NO_1D_GATHER,
    "rle_decode_packed_kernel": _NO_1D_GATHER,
}


@dataclasses.dataclass(frozen=True)
class DispatchPolicy:
    """Backend + size-threshold routing policy. All fields host-static."""

    use_pallas: Optional[bool] = None  # None = auto: TPU backends only
    # None = auto: interpret off the TPU, compiled on it. Deliberately no
    # environment variable: interpret mode on the chip is set in code only.
    interpret: Optional[bool] = None
    # bucketize: below this many queries the XLA searchsorted is cheaper
    # than staging boundaries into VMEM.
    bucketize_min_queries: int = 4096
    # segment_sum: the one-hot matmul needs the (G,) accumulator and a
    # (TILE, G) one-hot resident in VMEM.
    segment_sum_max_groups: int = MAX_MATMUL_SEGMENTS
    # sort-free grouping (groupby.grouping): scatter over the mixed-radix
    # key domain instead of argsort-unique, when every group key has
    # ingest-recorded domain metadata and the product domain fits.
    enable_sort_free: bool = True
    sort_free_max_domain: int = 1 << 20
    # top-k (order.py row-level path): below this many rows lax.top_k's
    # fused sort wins; above the kernel's partial-bitonic tiles pay off.
    topk_min_rows: int = 4096
    topk_max_k: int = MAX_KERNEL_K
    # entry-level ordering (order.py): sort/select RLE columns by RUNS and
    # bounded-domain keys by histogram ranks instead of row-level sorts.
    # Off -> every ORDER BY decodes to rows first (the paper's row-level
    # baseline; benchmarks/bench_orderby.py measures the gap).
    enable_entry_order: bool = True
    # bit packing (DESIGN.md §11): ingest-time sub-byte packing of integer
    # buffers (consulted by compress.encode when the caller requests
    # pack=True) + trace-time unpack routing. ``pack_max_bits`` bounds
    # which domains pack — above it the 32->bits transfer saving no longer
    # pays for the shift+mask work; 24 bits = a guaranteed >= 25% cut.
    enable_pack: bool = True
    pack_max_bits: int = 24
    # below this many values the standalone unpack is latency-bound and
    # the inline XLA expression wins even on TPU.
    unpack_min_vals: int = 4096
    # streamed out-of-core pipeline (core/stream.py, DESIGN.md §12): how
    # many partitions the executor transfers (and, on the aggregate path,
    # dispatches) AHEAD of the one whose partial is being merged. 0 = the
    # fully synchronous reference mode, 1 = the seed's double buffering,
    # 2 = default (hide transfer AND merge behind compute). Clamped at
    # run time against a table's declared device-memory budget.
    prefetch_depth: int = 2
    # query-serving layer (core/serve.py, DESIGN.md §13): device-residency
    # LRU byte budget (None = the served table's declared budget, falling
    # back to unbounded), jitted-plan cache capacity (distinct query
    # shapes held warm), and the admission loop's shared-scan batch bound
    # (how many compatible queued queries one streamed pass may serve).
    serve_budget_bytes: Optional[int] = None
    plan_cache_size: int = 32
    serve_max_batch: int = 8
    # telemetry (core/telemetry.py, DESIGN.md §14): span/trace recording.
    # Off by default — every span site then costs one policy-field read;
    # bench_stream CI-gates that the disabled path stays <2% of wall.
    # ``trace_buffer_events`` bounds the event ring (oldest drop beyond).
    enable_trace: bool = False
    trace_buffer_events: int = 1 << 16
    # fault tolerance (core/faults.py, core/stream.py, DESIGN.md §15):
    # ``enable_fault_injection`` gates the deterministic fault harness —
    # off, every probe site costs one policy-field read (entering a
    # FaultPlan scope flips it on). ``transfer_retries`` bounds how many
    # times a TransientTransferError is retried per partition transfer;
    # ``transfer_backoff_ms`` is the first retry's delay, doubling each
    # further attempt (exponential backoff).
    enable_fault_injection: bool = False
    transfer_retries: int = 3
    transfer_backoff_ms: float = 10.0

    def pallas_enabled(self) -> bool:
        if self.use_pallas is not None:
            return self.use_pallas
        return jax.default_backend() == "tpu"

    def interpret_mode(self) -> bool:
        if self.interpret is not None:
            return self.interpret
        return jax.default_backend() != "tpu"


def _env_tristate(env, name: str) -> Optional[bool]:
    raw = env.get(name, "auto").strip().lower()
    if raw in ("1", "true", "yes", "on"):
        return True
    if raw in ("0", "false", "no", "off"):
        return False
    return None  # auto


def _env_int(env, name: str, default: int) -> int:
    raw = env.get(name)
    if raw is None:
        return default
    return int(raw)


def _env_opt_int(env, name: str, default: Optional[int]) -> Optional[int]:
    raw = env.get(name)
    if raw is None or raw.strip().lower() in ("", "none", "auto"):
        return default
    return int(raw)


def _env_float(env, name: str, default: float) -> float:
    raw = env.get(name)
    if raw is None:
        return default
    return float(raw)


def policy_from_env(env=None) -> DispatchPolicy:
    """Build a policy from environment variables (see module docstring)."""
    env = os.environ if env is None else env
    base = DispatchPolicy()
    sort_free = _env_tristate(env, "REPRO_SORT_FREE")
    entry_order = _env_tristate(env, "REPRO_ENTRY_ORDER")
    pack = _env_tristate(env, "REPRO_PACK")
    return DispatchPolicy(
        use_pallas=_env_tristate(env, "REPRO_USE_PALLAS"),
        bucketize_min_queries=_env_int(
            env, "REPRO_BUCKETIZE_MIN_QUERIES", base.bucketize_min_queries),
        segment_sum_max_groups=_env_int(
            env, "REPRO_SEGSUM_MAX_GROUPS", base.segment_sum_max_groups),
        enable_sort_free=True if sort_free is None else sort_free,
        sort_free_max_domain=_env_int(
            env, "REPRO_SORT_FREE_MAX_DOMAIN", base.sort_free_max_domain),
        topk_min_rows=_env_int(env, "REPRO_TOPK_MIN_ROWS", base.topk_min_rows),
        topk_max_k=_env_int(env, "REPRO_TOPK_MAX_K", base.topk_max_k),
        enable_entry_order=True if entry_order is None else entry_order,
        enable_pack=True if pack is None else pack,
        pack_max_bits=_env_int(env, "REPRO_PACK_MAX_BITS", base.pack_max_bits),
        unpack_min_vals=_env_int(env, "REPRO_UNPACK_MIN_VALS",
                                 base.unpack_min_vals),
        prefetch_depth=_env_int(env, "REPRO_PREFETCH_DEPTH",
                                base.prefetch_depth),
        serve_budget_bytes=_env_opt_int(env, "REPRO_SERVE_BUDGET_BYTES",
                                        base.serve_budget_bytes),
        plan_cache_size=_env_int(env, "REPRO_PLAN_CACHE_SIZE",
                                 base.plan_cache_size),
        serve_max_batch=_env_int(env, "REPRO_SERVE_MAX_BATCH",
                                 base.serve_max_batch),
        enable_trace=bool(_env_tristate(env, "REPRO_TRACE")),
        trace_buffer_events=_env_int(env, "REPRO_TRACE_BUFFER",
                                     base.trace_buffer_events),
        enable_fault_injection=bool(_env_tristate(env, "REPRO_FAULTS")),
        transfer_retries=_env_int(env, "REPRO_TRANSFER_RETRIES",
                                  base.transfer_retries),
        transfer_backoff_ms=_env_float(env, "REPRO_TRANSFER_BACKOFF_MS",
                                       base.transfer_backoff_ms),
    )


_POLICY: DispatchPolicy = policy_from_env()


def policy() -> DispatchPolicy:
    return _POLICY


def set_policy(p: DispatchPolicy) -> None:
    global _POLICY
    _POLICY = p


@contextlib.contextmanager
def overrides(**kw):
    """Temporarily replace policy fields (tests / benchmarks)."""
    old = _POLICY
    set_policy(dataclasses.replace(old, **kw))
    try:
        yield _POLICY
    finally:
        set_policy(old)


# ---------------------------------------------------------------------------
# Routed primitives. Callable from inside jitted programs: the routing
# decision is static, the chosen implementation traces inline.
# ---------------------------------------------------------------------------


def _route(primitive: str, path: str, reason: str) -> None:
    if not _POLICY.enable_trace:
        return
    # lazy import, same layering reason as _is_packed: telemetry lives in
    # core but reads this module's policy
    from repro.core import telemetry
    telemetry.record_route(primitive, path, reason)


def _kernel_ok(*arrays) -> bool:
    return all(a.dtype in _KERNEL_DTYPES for a in arrays)


def _is_packed(x) -> bool:
    # lazy import: dispatch sits below core in the layering, but the
    # PackedColumn leaf lives with the other encodings
    from repro.core.encodings import PackedColumn
    return isinstance(x, PackedColumn)


def unpack(packed) -> jax.Array:
    """Expand a ``PackedColumn`` buffer leaf to its logical int32 values.

    Pallas shift+mask kernel when the policy allows and the stream clears
    the size threshold, else the inline XLA expression (``ref_unpack``) —
    which traces at the CALLER, so XLA fuses the extraction into the
    consuming op instead of materializing the full-width tensor.
    """
    pol = policy()
    n, words = packed.nrows, packed.words
    if pol.pallas_enabled() and n >= pol.unpack_min_vals and words.shape[0]:
        _route("unpack", "kernel",
               f"n={n}>=unpack_min_vals={pol.unpack_min_vals}")
        return unpack_kernel(words, packed.bit_width, packed.offset, n,
                             interpret=pol.interpret_mode())
    _route("unpack", "ref",
           "pallas off" if not pol.pallas_enabled()
           else f"n={n}<unpack_min_vals={pol.unpack_min_vals}"
           if n < pol.unpack_min_vals else "empty words")
    return ref_mod.ref_unpack(words, packed.bit_width, packed.offset, n)


def direct_lookup(table: jax.Array, index: jax.Array) -> jax.Array:
    """``table[index]``, the direct-address PK-FK probe (core/join.py): one
    row-sized gather from a map over the fact key's domain, in place of a
    binary search over the sorted build side. XLA's gather on every
    backend: a Pallas twin would rest on the 1-D gather Mosaic refuses
    (``OFF_TPU_ROUTE``)."""
    _route("join_probe", "direct", f"map slots={table.shape[0]}")
    return table[index]


def bucketize(boundaries: jax.Array, queries, right: bool = True) -> jax.Array:
    """torch.bucketize == searchsorted (right=True -> side='right').

    ``queries`` may be a ``PackedColumn``; it is unpacked first (``unpack``
    routes that). The counting kernel takes the call when the policy
    allows, the queries clear the size threshold and the boundaries fit
    one tile; XLA's searchsorted takes every other. The resident-boundary
    binary-search kernels are off the route (``OFF_TPU_ROUTE``).
    """
    pol = policy()
    if _is_packed(queries):
        queries = unpack(queries)
    n_b, n_q = boundaries.shape[0], queries.shape[0]
    if (pol.pallas_enabled() and 0 < n_b <= COUNT_KERNEL_MAX_BOUNDARIES
            and n_q >= pol.bucketize_min_queries
            and _kernel_ok(boundaries, queries)):
        _route("bucketize", "count_kernel",
               f"n_q={n_q}>=bucketize_min_queries="
               f"{pol.bucketize_min_queries}, n_b={n_b}<="
               f"{COUNT_KERNEL_MAX_BOUNDARIES}")
        return bucketize_count_kernel(boundaries, queries, right,
                                      interpret=pol.interpret_mode())
    _route("bucketize", "xla",
           "pallas off" if not pol.pallas_enabled()
           else f"n_q={n_q}<bucketize_min_queries={pol.bucketize_min_queries}"
           if n_q < pol.bucketize_min_queries
           else f"n_b={n_b} outside (0, {COUNT_KERNEL_MAX_BOUNDARIES}]"
           if not 0 < n_b <= COUNT_KERNEL_MAX_BOUNDARIES else "dtype")
    side = "right" if right else "left"
    return jnp.searchsorted(boundaries, queries, side=side).astype(jnp.int32)


def _dense_fuses() -> bool:
    """Whether this backend runs the dense form fused. XLA:TPU keeps the
    (G, rows) compare inside its reduce; XLA:CPU builds it in memory (17.7
    GB of temporaries at G = 4096 and 2^20 rows) and scatters in O(rows),
    so there the scatter stays (DESIGN.md §5)."""
    return jax.default_backend() == "tpu"


def _why_not_dense(num_segments: int) -> Optional[str]:
    """None when a reduction into ``num_segments`` slots takes the dense
    form; otherwise why it keeps XLA's scatter."""
    if num_segments > G_DENSE:
        return f"G={num_segments}>G_DENSE={G_DENSE}"
    if not _dense_fuses():
        return f"backend {jax.default_backend()} builds the dense compare"
    return None


# jitted so that an eager caller dispatches one program, not five ops;
# inside a traced program they inline
@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _dense_segment_reduce(reduce: str, values: jax.Array,
                          segment_ids: jax.Array, num_segments: int,
                          identity) -> jax.Array:
    """``out[g] = reduce(values[i] for i with segment_ids[i] == g)``, as a
    broadcast compare against ``iota(G)`` reduced along the rows. The
    (G, rows) operand lives only inside XLA's reduce fusion: rows sit on
    the lanes, so G rides the sublanes and nothing row-sized is indexed."""
    slots = jnp.arange(num_segments, dtype=segment_ids.dtype)[:, None]
    hit = jnp.where(segment_ids[None, :] == slots, values[None, :],
                    jnp.asarray(identity, values.dtype))
    if reduce == "sum":
        return jnp.sum(hit, axis=1, dtype=values.dtype)
    fn = jnp.min if reduce == "min" else jnp.max
    return fn(hit, axis=1, initial=identity)


def segment_sum(values: jax.Array, segment_ids: jax.Array,
                num_segments: int) -> jax.Array:
    """Segment sum; out-of-range ids (capacity padding) contribute 0.

    Integer values over at most ``G_DENSE`` groups, on the TPU: the dense
    reduction (exact, like the scatter it replaces). Float32: the MXU
    one-hot matmul when the policy allows and the group count fits a VMEM
    block (its accumulator is float32, so integers never go there).
    Everything else: XLA scatter-add, whose summation order float callers
    keep.
    """
    pol = policy()
    integer = jnp.issubdtype(values.dtype, jnp.integer)
    why_not = _why_not_dense(num_segments)
    if integer and why_not is None:
        _route("segment_sum", "dense", f"G={num_segments}<=G_DENSE={G_DENSE}")
        return _dense_segment_reduce("sum", values, segment_ids,
                                     num_segments, 0)
    if (pol.pallas_enabled() and values.dtype == jnp.float32
            and 0 < num_segments <= pol.segment_sum_max_groups
            and values.shape[0] > 0):
        _route("segment_sum", "kernel",
               f"G={num_segments}<=segment_sum_max_groups="
               f"{pol.segment_sum_max_groups}")
        return segment_sum_kernel(values, segment_ids, num_segments,
                                  interpret=pol.interpret_mode())
    _route("segment_sum", "xla_scatter",
           f"{why_not}; {values.dtype} keeps exact scatter arithmetic"
           if integer
           else "pallas off" if not pol.pallas_enabled()
           else f"dtype {values.dtype}" if values.dtype != jnp.float32
           else f"G={num_segments} outside "
           f"(0, segment_sum_max_groups={pol.segment_sum_max_groups}]")
    return jnp.zeros((num_segments,), values.dtype).at[segment_ids].add(
        values, mode="drop")


def _extreme_identity(reduce: str, dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.inf if reduce == "min" else -jnp.inf
    info = jnp.iinfo(dtype)
    return info.max if reduce == "min" else info.min


def _segment_extreme(reduce: str, values: jax.Array, segment_ids: jax.Array,
                     num_segments: int) -> jax.Array:
    identity = _extreme_identity(reduce, values.dtype)
    why_not = _why_not_dense(num_segments)
    if why_not is None:
        _route(f"segment_{reduce}", "dense",
               f"G={num_segments}<=G_DENSE={G_DENSE}")
        return _dense_segment_reduce(reduce, values, segment_ids,
                                     num_segments, identity)
    _route(f"segment_{reduce}", "xla_scatter", why_not)
    init = jnp.full((num_segments,), identity, values.dtype)
    at = init.at[segment_ids]
    return (at.min if reduce == "min" else at.max)(values, mode="drop")


def segment_min(values: jax.Array, segment_ids: jax.Array,
                num_segments: int) -> jax.Array:
    """Segment minimum; empty groups and out-of-range ids give the dtype's
    identity (+inf, or the integer maximum). Dense reduction over at most
    ``G_DENSE`` groups on the TPU, XLA scatter-min otherwise; both exact."""
    return _segment_extreme("min", values, segment_ids, num_segments)


def segment_max(values: jax.Array, segment_ids: jax.Array,
                num_segments: int) -> jax.Array:
    """Segment maximum; the mirror of ``segment_min``."""
    return _segment_extreme("max", values, segment_ids, num_segments)


def topk(values: jax.Array, k: int):
    """Top-k (descending) of a 1-D rank-key tensor: ``(vals[k], idx[k])``.

    Ties resolve to the lowest index on BOTH implementations (pandas-stable
    descending order); ascending callers flip the rank key (order.py).
    Routes to the partial-bitonic Pallas kernel when the policy allows and
    (rows, k) clear the thresholds, else ``jax.lax.top_k``.
    """
    pol = policy()
    if (pol.pallas_enabled() and values.shape[0] >= pol.topk_min_rows
            and 1 <= k <= min(pol.topk_max_k, MAX_KERNEL_K)
            and _kernel_ok(values)):
        _route("topk", "kernel",
               f"rows={values.shape[0]}>=topk_min_rows={pol.topk_min_rows}, "
               f"k={k}<=topk_max_k={min(pol.topk_max_k, MAX_KERNEL_K)}")
        return topk_kernel(values, k, interpret=pol.interpret_mode())
    _route("topk", "xla",
           "pallas off" if not pol.pallas_enabled()
           else f"rows={values.shape[0]}<topk_min_rows={pol.topk_min_rows}"
           if values.shape[0] < pol.topk_min_rows
           else f"k={k} outside kernel range")
    return jax.lax.top_k(values, k)
