"""Async pipelined streaming executor (DESIGN.md §12).

The out-of-core path's cost is three overlappable stages per partition —
host->device transfer, the fused device program, and the host-side partial
merge — plus the jit dispatch glue between them. The seed executor
double-buffered at a hard-coded depth of 1 and serialized every merge
after the loop, so the transfer and merge stages sat on the critical path
and bit-packing's smaller transfers could never pay for their unpack
compute. This module turns the per-partition loop into a depth-``k``
software pipeline:

  * ``pipelined_fold`` — a prefetch ring of up to ``depth`` partitions
    transferred ahead (on a dedicated transfer thread, so the copy
    genuinely overlaps device execution) of the one whose partial is
    being folded on the host, with exactly ONE device program dispatched
    beyond the partial being drained: the next program is dispatched
    between blocking on partial ``i`` and folding it, so the device runs
    ``i+1`` while the host merges ``i`` and partitions ``i+2..i+k``
    stream in. Never more than one program is enqueued ahead — on
    backends whose executions contend for the same execution units
    (XLA:CPU's shared intra-op pool), concurrently enqueued programs
    slow each other down more than the overlap saves. ``depth=0`` is the
    fully synchronous reference mode (transfer, compute, block, merge —
    the no-overlap point the stream bench sweeps against);

  * ``pipelined_ranked_fold`` — the ranked (ORDER BY / TOP-K) variant:
    transfers are issued speculatively up to ``depth`` ahead under the
    pruning bound known at issue time, but execution is gated by a
    re-check at the head of the ring once earlier merges have tightened
    the bound. Because the bound only ever tightens, the executed set is
    EXACTLY the sequential path's — a wasted prefetch is bytes, never a
    dispatched program and never a wrong result;

  * ``clamp_depth`` — budget awareness: the ring's in-flight encoded
    copies are clamped against the device-memory budget the table was
    sized for (``rows_for_budget``), instead of silently overshooting it
    by ``depth × max_partition_nbytes``.

Merges fold in deterministic partition order regardless of depth, so
results are bit-identical at every depth (tests/test_stream.py asserts
depth 0/1/4 equality across all six encodings). Stage wall times are
recorded per run (``StreamStats``): ``h2d_ms`` / ``compute_ms`` /
``merge_ms`` are MAIN-thread wall time spent waiting on transfers,
dispatching + waiting on device programs, and folding partials
respectively (``d2h_ms``, the part of ``merge_ms`` spent fetching each
partial to the host in one ``jax.device_get``) — a fully hidden transfer
shows up as ``h2d_ms ~ 0``, and under overlap the three need not sum to
the elapsed wall time. Each stage is a scoped region (``_Stages``); with
tracing enabled (``REPRO_TRACE``, DESIGN.md §14) it is a telemetry span
that feeds its stat from its own timestamp pair, so ``StreamStats``, the
ring and the profiler's ``repro:`` annotations reconcile by construction.

Fault tolerance (DESIGN.md §15): both drivers probe the fault-injection
harness (``faults.maybe_inject``) at their three per-partition stages,
retry ``TransientTransferError`` with exponential backoff
(``transfer_retries`` / ``transfer_backoff_ms``), and respond to
``DeviceOOMError`` by retiring the prefetch ring, halving the depth
(floor: the synchronous depth-0 mode) and resuming from the failed
partition — folds are strictly in order, so the carried accumulator is
exact and recovered results stay bit-identical to a fault-free run. Any
terminal error leaves the ring CLEAN: queued transfer futures are
cancelled before the pool shuts down, and ``StreamStats`` (including
``retries`` / ``degradations``) is final whether the driver returned or
raised.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax

from repro.core import faults, telemetry
from repro.core.faults import DeviceOOMError, TransientTransferError


@dataclasses.dataclass
class StreamStats:
    """Per-run pipeline observability (surfaced via ``last_stats``)."""

    prefetch_depth: int = 0  # effective (post-clamp) depth this run used
    h2d_ms: float = 0.0  # main-thread wait on transfers (hidden -> ~0)
    compute_ms: float = 0.0  # dispatching programs + blocking on partials
    merge_ms: float = 0.0  # folding partials on the host, d2h_ms included
    d2h_ms: float = 0.0  # fetching partials to the host (jax.device_get)
    inflight_bytes_max: int = 0  # peak bytes transferred-but-not-yet-folded
    transferred: int = 0  # device_put calls issued
    executed: int = 0  # device programs dispatched
    # serving attribution (core/serve.py, DESIGN.md §13). On a served run
    # these split where a query's partitions came from: ``lru_hits`` were
    # already device-resident (no device_put at all), ``shared_hits`` were
    # transferred by a co-batched query in the same shared pass, and
    # ``transferred`` narrows to the copies THIS query triggered — so
    # summing ``transferred`` across a batch matches the pass's actual
    # device_put count. Standalone PartitionedQuery runs leave both at 0.
    lru_hits: int = 0
    shared_hits: int = 0
    # fault tolerance (DESIGN.md §15): transfer retries performed after
    # TransientTransferErrors, and depth halvings performed after
    # DeviceOOMErrors (``prefetch_depth`` reflects the FINAL depth)
    retries: int = 0
    degradations: int = 0
    # query id the run's trace spans are tagged with (telemetry.next_qid
    # via plan.Query; None on runs driven outside the query layer)
    qid: Optional[int] = None

    def as_dict(self) -> dict:
        # generic over the dataclass fields so a field can never again be
        # populated-but-dropped (the seed's as_dict silently omitted
        # ``executed`` from every bench JSON; tests/test_telemetry.py pins
        # completeness)
        out = {}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            out[f.name] = round(v, 3) if f.name.endswith("_ms") else v
        return out


_EMPTY: dict = {}


def emit_stage(tel, stats: StreamStats, field: Optional[str], name: str,
               t0: float, t1: float, track: str = "main",
               attrs: dict = _EMPTY) -> None:
    """Fold one stage interval, timed by the caller, into ``stats`` AND
    record it as a ring span (the serving layer's per-subscriber spans).

    ``tel`` is the resolved registry or None (tracing disabled — only the
    stats add happens); ``field=None`` records a span with no stats
    counterpart."""
    if field is not None:
        setattr(stats, field, getattr(stats, field) + (t1 - t0) * 1e3)
    if tel is not None:
        tel.record(name, t0, t1 - t0, track, qid=stats.qid, **attrs)


_STAGE_FIELDS = ("h2d_ms", "compute_ms", "merge_ms", "d2h_ms")


class _Stages:
    """The scoped stage regions of one executor pass.

    ``stage(field, name, attrs)`` is a ``with`` region. Traced, it is a
    telemetry span (ring event + ``repro:<name>`` profiler annotation,
    child of the span open on the thread) that adds its milliseconds to
    ``stats.<field>`` from its own timestamp pair; untraced, a reused
    ``telemetry.SinkClock`` adds the same milliseconds and nothing else."""

    def __init__(self, tel, stats: StreamStats):
        self.tel = tel
        self.stats = stats
        self.clocks = ({f: telemetry.SinkClock(stats, f)
                        for f in _STAGE_FIELDS}
                       if tel is None else None)
        # the transfer thread's spans name the query's root explicitly
        self.root = telemetry.current_span_id() if tel is not None else None

    def __call__(self, field: str, name: str, attrs: dict = _EMPTY,
                 track: str = "main"):
        if self.tel is None:
            return self.clocks[field]
        return self.tel.span(name, track, sink=(self.stats, field),
                             qid=self.stats.qid, **attrs)

    def issue(self):
        """The client handing partitions to the transfer thread, and
        retiring the thread at the end of the pass. On a fresh pool the
        first hand-off starts the thread, which takes the interpreter for
        its first copy while the client waits for it back. No stats field:
        it is not a wait on a transfer's completion."""
        if self.tel is None:
            return telemetry.NULL_SPAN
        return self.tel.span("h2d_issue", qid=self.stats.qid)

    def transfer(self, attrs: dict):
        """The copy-issue window on the transfer thread (no stats field:
        the main thread's wait is ``h2d_wait``)."""
        return self.tel.span("transfer", "transfer", parent=self.root,
                             qid=self.stats.qid, **attrs)

    def fold(self, fold: Callable, acc, item, partial, part, attrs: dict):
        """``fold(acc, item, partial)`` as the ``fold`` stage. The partial
        comes to the host first in ONE ``jax.device_get`` (every leaf's
        copy issued before any is awaited), the ``d2h`` child stage."""
        with self("merge_ms", "fold", attrs):
            faults.maybe_inject("fold", part)
            with self("d2h_ms", "d2h", attrs):
                host = jax.device_get(partial)
            return fold(acc, item, host)

    def program(self, disp, blk, attrs: dict) -> None:
        """The program's dispatch->retire window on the device track: from
        the end of its ``dispatch`` to the end of its ``block``. Ring only
        (its halves are the annotated, stat-feeding stages)."""
        if self.tel is not None:
            self.tel.record("program", disp.t1, blk.t1 - disp.t1, "device",
                            qid=self.stats.qid, **attrs)


class _TransferPool(ThreadPoolExecutor):
    """The one transfer thread of a executor pass. Its shutdown on leaving
    the ``with`` is client time on the thread's account, so it runs as an
    ``h2d_issue`` stage, like the hand-off that started it."""

    def __init__(self, stage: _Stages):
        super().__init__(max_workers=1)
        self._stage = stage

    def __exit__(self, *exc):
        with self._stage.issue():
            return super().__exit__(*exc)


def clamp_depth(depth: int, max_part_nbytes: int,
                budget_bytes: Optional[int]) -> int:
    """Clamp the prefetch depth against the declared device-memory budget.

    ``rows_for_budget`` sizes ONE partition's working set to the budget;
    the prefetch ring adds up to ``depth`` encoded in-flight copies on
    top. Those extra copies are allowed one further budget's worth of
    memory (the seed's double-buffer already implied one undeclared copy)
    — beyond that the depth is clamped with a warning rather than
    silently overshooting the budget the caller asked for. Tables ingested
    without a budget (``budget_bytes=None``) are never clamped.
    """
    depth = max(int(depth), 0)
    if budget_bytes is None or max_part_nbytes <= 0 or depth <= 1:
        return depth
    fit = max(int(budget_bytes) // int(max_part_nbytes), 1)
    if depth > fit:
        warnings.warn(
            f"prefetch_depth={depth} would keep "
            f"{depth} x {max_part_nbytes} = {depth * max_part_nbytes} "
            f"in-flight bytes against a {budget_bytes}-byte device budget; "
            f"clamping to depth {fit} (REPRO_PREFETCH_DEPTH / "
            "DispatchPolicy.prefetch_depth)", stacklevel=3)
        return fit
    return depth


def _block(x) -> None:
    jax.block_until_ready(x)


# ---------------------------------------------------------------------------
# Fault handling (DESIGN.md §15)
# ---------------------------------------------------------------------------


class _Restart(Exception):
    """Internal carrier for OOM depth-degradation (never escapes this
    module): holds the cause, the accumulator folded so far, and the
    position of the partition whose transfer/compute/fold cycle failed.
    Folds are strictly in order, so ``acc`` covers exactly
    ``items[start:pos]`` and the outer driver can retire the ring, halve
    the depth, and resume from ``pos`` without re-folding anything."""

    def __init__(self, cause: BaseException, acc, pos: int):
        super().__init__(str(cause))
        self.cause = cause
        self.acc = acc
        self.pos = pos


def _degrade(depth: int, cause: BaseException, stats: StreamStats) -> int:
    """Halve the prefetch depth after a DeviceOOMError (floor 0 = the
    synchronous reference mode); at the floor the OOM is terminal."""
    if depth <= 0:
        raise cause
    new_depth = depth // 2
    stats.degradations += 1
    stats.prefetch_depth = new_depth
    telemetry.record_fault("degrade", qid=stats.qid, depth_from=depth,
                           depth_to=new_depth, cause=type(cause).__name__)
    return new_depth


def _transfer_with_retry(transfer: Callable, item, part,
                         stats: StreamStats):
    """One transfer through the injection probe + bounded exponential
    backoff on ``TransientTransferError`` (the only retryable class —
    ``DeviceOOMError`` degrades instead, anything else is terminal)."""
    from repro.kernels import dispatch
    pol = dispatch.policy()
    retries = max(int(pol.transfer_retries), 0)
    backoff_s = max(float(pol.transfer_backoff_ms), 0.0) * 1e-3
    attempt = 0
    while True:
        try:
            faults.maybe_inject("transfer", part)
            return transfer(item)
        except TransientTransferError as exc:
            if attempt >= retries:
                raise
            delay = backoff_s * (2 ** attempt)
            attempt += 1
            stats.retries += 1
            telemetry.record_fault("retry", qid=stats.qid, part=part,
                                   attempt=attempt,
                                   backoff_ms=round(delay * 1e3, 3),
                                   error=str(exc))
            if delay > 0:
                time.sleep(delay)


def pipelined_fold(items: Sequence, transfer: Callable, compute: Callable,
                   fold: Callable, init, depth: int, stats: StreamStats,
                   nbytes_of: Optional[Callable] = None,
                   label_of: Optional[Callable] = None):
    """Run ``fold(acc, item, compute(item, transfer(item)))`` over ``items``
    as a depth-``depth`` software pipeline; returns the final ``acc``.

    ``transfer(item)`` issues the (async) host->device copy;
    ``compute(item, cols)`` dispatches the fused device program and
    returns its (async) result; ``fold(acc, item, partial)`` consumes the
    partial on the host — it may block on device values. Items are folded
    strictly in sequence order at every depth, so any associative-in-order
    merge yields bit-identical results regardless of overlap.

    ``depth=0`` serializes every stage (and blocks on each partial before
    folding) — the reference point for the overlap benchmark. With
    ``depth >= 1``, up to ``depth`` transfers beyond the fold head are
    in flight on a dedicated transfer thread, and exactly one device
    program runs ahead of the partial being folded: it is dispatched
    after blocking on partial ``i`` and before folding it, so the fold
    and the next program overlap without ever enqueueing two programs
    against each other (drain included — no global barrier).

    ``label_of(item)`` (optional) names the partition in trace spans'
    ``part`` attr and in fault-injection coordinates (falling back to the
    item's position). All spans carry ``stats.qid``.

    Fault behavior (DESIGN.md §15): transient transfer failures retry
    with backoff; a ``DeviceOOMError`` at any stage retires the ring,
    halves ``depth`` and resumes from the failed partition (terminal at
    depth 0); any terminal error cancels the queued ring futures before
    propagating, so no transfer outlives the call.
    """
    tel = telemetry.registry() if telemetry.enabled() else None
    pos, acc = 0, init
    while True:
        try:
            return _fold_pipeline(items, pos, acc, transfer, compute, fold,
                                  depth, stats, nbytes_of, label_of, tel)
        except _Restart as r:
            depth = _degrade(depth, r.cause, stats)
            pos, acc = r.pos, r.acc


def _fold_pipeline(items, start, acc, transfer, compute, fold, depth,
                   stats, nbytes_of, label_of, tel):
    """One pass of ``pipelined_fold`` from position ``start``; raises
    ``_Restart`` on a recoverable DeviceOOMError."""

    def part_of(i):
        return label_of(items[i]) if label_of is not None else i

    def attr(item):
        if tel is None or label_of is None:
            return _EMPTY
        return {"part": label_of(item)}

    def xfer(i):
        return _transfer_with_retry(transfer, items[i], part_of(i), stats)

    stage = _Stages(tel, stats)

    if depth <= 0:
        i = start
        try:
            while i < len(items):
                item = items[i]
                a = attr(item)
                with stage("h2d_ms", "transfer", a, "transfer"):
                    cols = xfer(i)
                    _block(cols)
                with stage("compute_ms", "program", a, "device"):
                    faults.maybe_inject("compute", part_of(i))
                    partial = compute(item, cols)
                    _block(partial)
                acc = stage.fold(fold, acc, item, partial, part_of(i), a)
                stats.transferred += 1
                stats.executed += 1
                if nbytes_of is not None:
                    stats.inflight_bytes_max = max(stats.inflight_bytes_max,
                                                   nbytes_of(item))
                i += 1
        except DeviceOOMError as exc:
            # at depth 0 _degrade re-raises; the carrier keeps one shape
            raise _Restart(exc, acc, i) from None
        return acc

    ring: deque = deque()  # (pos, item, future cols): transfers in flight
    pending = None  # (pos, item, async partial, t_disp): ONE dispatched
    idx = start
    head = start  # position of the next unfolded item (restart point)
    inflight = 0

    def do_transfer(i):
        # runs on the worker thread; the span is the copy-issue window
        # there, rendered on the transfer track
        if tel is None:
            return xfer(i)
        with stage.transfer(attr(items[i])):
            return xfer(i)

    with _TransferPool(stage) as pool:
        try:

            def top_up():
                # the dispatched-but-unfolded program occupies a ring slot
                # too: at most depth+1 partitions live beyond the fold
                # head, exactly the budget clamp_depth accounts for
                nonlocal idx, inflight
                while (len(ring) + (pending is not None) < depth + 1
                       and idx < len(items)):
                    item = items[idx]
                    ring.append((idx, item, pool.submit(do_transfer, idx)))
                    idx += 1
                    stats.transferred += 1
                    if nbytes_of is not None:
                        inflight += nbytes_of(item)
                        stats.inflight_bytes_max = max(
                            stats.inflight_bytes_max, inflight)

            def dispatch_head():
                i, item, fut = ring.popleft()
                a = attr(item)
                with stage("h2d_ms", "h2d_wait", a):
                    cols = fut.result()  # ~0 when the copy hid behind compute
                with stage("compute_ms", "dispatch", a) as disp:
                    faults.maybe_inject("compute", part_of(i))
                    partial = compute(item, cols)
                stats.executed += 1
                return i, item, partial, disp

            with stage.issue():
                top_up()
            if ring:
                pending = dispatch_head()
            while pending is not None:
                i, item, partial, disp = pending
                head = i  # acc covers items[start:i]
                a = attr(item)
                with stage("compute_ms", "block", a) as blk:
                    _block(partial)  # the device is the gate
                stage.program(disp, blk, a)
                # program ``i`` retired: launch ``i+1`` BEFORE folding
                # ``i`` so the fold runs under the next program
                pending = dispatch_head() if ring else None
                acc = stage.fold(fold, acc, item, partial, part_of(i), a)
                head = i + 1
                if nbytes_of is not None:
                    inflight -= nbytes_of(item)
                # the fold head advanced: replenish the transfer ring
                # (copies run on the worker while the next program runs)
                with stage.issue():
                    top_up()
        except DeviceOOMError as exc:
            raise _Restart(exc, acc, head) from None
        finally:
            # terminal or restarting: cancel queued copies so nothing the
            # caller will never fold still runs under the pool shutdown.
            # (The one possibly-running transfer finishes and is dropped;
            # a restart re-transfers into FRESH buffers, so donated
            # buffers are never reused.)
            for _, _, fut in ring:
                fut.cancel()
            ring.clear()
    return acc


def pipelined_ranked_fold(items: Sequence, transfer: Callable,
                          compute: Callable, fold: Callable,
                          prune: Callable, depth: int,
                          stats: StreamStats,
                          nbytes_of: Optional[Callable] = None,
                          label_of: Optional[Callable] = None
                          ) -> Tuple[object, int, int]:
    """Ranked (TOP-K) pipeline: speculative prefetch, bound-gated execution.

    ``items`` must arrive best-zone-first; ``prune(state, item)`` is True
    when the CURRENT merged state's k-th-best bound proves ``item`` cannot
    contribute. Transfers are issued up to ``depth`` ahead under the bound
    known at issue time — the next best-zone partitions stream in while
    the current merge tightens the bound — but each item is re-checked
    when it reaches the head of the ring, and only then is its device
    program dispatched. The bound tightens monotonically, so:

      * an item prunable at issue time stays prunable (never transferred),
      * an item that the strictly sequential executor would have pruned
        is pruned at the head re-check here — speculation wastes at most
        ``depth`` transfers' worth of BYTES, never an execution and never
        a result (tests/test_stream.py asserts the executed set matches
        depth 0 exactly).

    Returns ``(state, ranked_skipped, prefetch_wasted)`` where
    ``prefetch_wasted`` counts transferred-then-pruned items (a subset of
    ``ranked_skipped``).

    Fault behavior matches ``pipelined_fold`` (DESIGN.md §15): transient
    transfer retries, OOM depth-degradation resuming from the failed
    partition (per-item decisions re-checked — the bound only tightens,
    so nothing skipped un-skips), and ring cleanup on terminal errors.
    """
    tel = telemetry.registry() if telemetry.enabled() else None
    # per-position outcome ("issue"/"head" prune, "exec"), overwritten on
    # a degraded re-run so skip/waste counts never double-count an item
    decisions: Dict[int, str] = {}
    pos, state = 0, None
    while True:
        try:
            state = _ranked_pipeline(items, pos, state, transfer, compute,
                                     fold, prune, depth, stats, nbytes_of,
                                     label_of, tel, decisions)
            break
        except _Restart as r:
            depth = _degrade(depth, r.cause, stats)
            pos, state = r.pos, r.acc
    skipped = sum(1 for d in decisions.values() if d != "exec")
    wasted = sum(1 for d in decisions.values() if d == "head")
    return state, skipped, wasted


def _ranked_pipeline(items, start, state, transfer, compute, fold, prune,
                     depth, stats, nbytes_of, label_of, tel, decisions):
    """One pass of ``pipelined_ranked_fold`` from position ``start``;
    raises ``_Restart`` on a recoverable DeviceOOMError."""

    def part_of(i):
        return label_of(items[i]) if label_of is not None else i

    def attr(item):
        if tel is None or label_of is None:
            return _EMPTY
        return {"part": label_of(item)}

    stage = _Stages(tel, stats)

    def do_transfer(i):
        if tel is None:
            return _transfer_with_retry(transfer, items[i], part_of(i),
                                        stats)
        with stage.transfer(attr(items[i])):
            return _transfer_with_retry(transfer, items[i], part_of(i),
                                        stats)

    ring: deque = deque()  # (pos, item, future cols): not yet bound-gated
    idx = start
    head = start
    inflight = 0
    with _TransferPool(stage) as pool:
        try:
            while idx < len(items) or ring:
                with stage.issue():
                    while len(ring) < depth + 1 and idx < len(items):
                        i, item = idx, items[idx]
                        idx += 1
                        if prune(state, item):
                            decisions[i] = "issue"
                            if tel is not None:
                                tel.instant("ranked_prune", "main",
                                            qid=stats.qid, stage="issue",
                                            **attr(item))
                            continue
                        # speculative, off-thread: bytes at risk, not
                        # results
                        ring.append((i, item, pool.submit(do_transfer, i)))
                        stats.transferred += 1
                        if nbytes_of is not None:
                            inflight += nbytes_of(item)
                            stats.inflight_bytes_max = max(
                                stats.inflight_bytes_max, inflight)
                if not ring:
                    break
                i, item, fut = ring.popleft()
                head = i  # state covers every fold up to (not incl.) i
                if nbytes_of is not None:
                    inflight -= nbytes_of(item)
                if prune(state, item):  # merges since issue tightened it
                    decisions[i] = "head"
                    if tel is not None:
                        tel.instant("ranked_prune", "main", qid=stats.qid,
                                    stage="head", wasted_transfer=True,
                                    **attr(item))
                    fut.cancel()  # un-started copies are dropped entirely
                    continue
                a = attr(item)
                with stage("h2d_ms", "h2d_wait", a):
                    cols = fut.result()
                with stage("compute_ms", "dispatch", a) as disp:
                    faults.maybe_inject("compute", part_of(i))
                    partial = compute(item, cols)  # gated: pruned never run
                with stage("compute_ms", "block", a) as blk:
                    _block(partial)
                stage.program(disp, blk, a)
                state = stage.fold(fold, state, item, partial, part_of(i), a)
                stats.executed += 1
                decisions[i] = "exec"
        except DeviceOOMError as exc:
            raise _Restart(exc, state, head) from None
        finally:
            for _, _, fut in ring:
                fut.cancel()
            ring.clear()
    return state
