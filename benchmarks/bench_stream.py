"""Pipelined streaming executor benchmark (DESIGN.md §12): overlap
efficiency of the out-of-core path vs its compute-only lower bound.

The depth-``k`` prefetch ring overlaps host->device transfer, the fused
device program and the host-side partial merge. This harness measures how
much of that overlap is realized on the dict-heavy packed workload
(bench_compress's schema, where fused unpacking adds device work that the
pipeline must hide transfers behind):

  * ``compute_only_ms`` — the same fused program streamed over partitions
    ALREADY resident on the device (a separate non-donating jit of the
    program: donation would invalidate the resident buffers), dispatch
    back-to-back with one terminal block and no host merges. No transfer,
    no merge — the wall-clock floor any executor schedule can reach;
  * a prefetch-depth sweep 0/1/2/4 of warm end-to-end query wall time
    (depth 0 = fully synchronous reference — the no-overlap gap;
    depth 1 = the seed's double buffering; 2 = default), each with the
    per-stage ``last_stats`` breakdown;
  * ``overlap_efficiency`` = compute_only / wall at the DEFAULT depth —
    1.0 means transfers and merges are fully hidden. This is the CI-gated
    metric (check_regression on the committed quick baseline).

Emits ``artifacts/bench/BENCH_stream.json`` (``BENCH_stream_quick.json``
under ``--quick`` via benchmarks.run).

``chaos()`` is the companion fault-recovery pass (DESIGN.md §15): the
same workload under a seeded FaultPlan, asserting bit-identical
recovery at bounded cost — run by the CI ``chaos`` job, emitting
``BENCH_faults.json`` + the ``TRACE_faults.json`` event timeline.

    PYTHONPATH=src python -m benchmarks.bench_stream
"""
from __future__ import annotations

import json
import os

import numpy as np
import jax

from repro.core import compress
from repro.core import partition as partition_mod
from repro.core import telemetry
from repro.core.partition import PartitionedQuery, PartitionedTable
from repro.core.plan import col
from repro.kernels import dispatch
from benchmarks.bench_compress import make_dict_heavy
from benchmarks.common import (ART_DIR, count_h2d, device_info,
                               time_interleaved)

DEPTHS = (0, 1, 2, 4)
DEFAULT_DEPTH = 2


def _query(pt):
    return (PartitionedQuery(pt)
            .filter(col("units") < 90)  # selective but zone-unprunable
            .groupby(["a"], {"s": ("sum", "qty"), "c": ("count", None)},
                     num_groups_cap=1024))


def _compute_only_runner(pt):
    """Wall-clock floor: the fused per-partition program with every
    partition pre-resident, no transfers, no host merges."""
    q = _query(pt)
    key_sets = tuple(q._prepare_inputs())
    prog = jax.jit(q._counted_program())  # non-donating: buffers stay live
    todo = [p for p in pt.partitions if p.rows]
    resident = [partition_mod.device_put(p.table.columns) for p in todo]

    def run_all():
        return [prog(cols, key_sets, p.rows)
                for p, cols in zip(todo, resident)]

    return run_all


def run(n=2_000_000, num_partitions=16, out_name="BENCH_stream.json"):
    rng = np.random.default_rng(7)
    data = make_dict_heavy(rng, n)
    cfg = compress.CompressionConfig(plain_threshold=1000)
    pt = PartitionedTable.from_arrays(data, cfg=cfg,
                                      num_partitions=num_partitions,
                                      pack=True)

    q = _query(pt)
    q.run()  # trace + compile once; the sweep below is warm at every depth
    stats_by_depth = {}

    def at_depth(depth):
        def go():
            with dispatch.overrides(prefetch_depth=depth):
                out = q.run()
            stats_by_depth[depth] = dict(q.last_stats)
            return out
        return go

    def traced():
        # full trace recording ON: every span site allocates an event.
        # Interleaved against the trace-off depth-2 runner (both inside
        # an ``overrides`` block, so the policy-swap cost cancels) this
        # bounds the telemetry cost from above — the disabled path (the
        # default, one policy-field read per site) does strictly less
        # work than the enabled path timed here, so if even THIS ratio
        # stays under the CI gate, the instrumentation cannot have
        # regressed the untraced executor. The run emits ~100 events;
        # the default 65536-event ring absorbs every round untrimmed.
        with dispatch.overrides(enable_trace=True):
            return q.run()

    telemetry.reset()

    # the bound and every depth sample the same drift epochs
    # (common.time_interleaved): overlap_efficiency is a CI-gated RATIO
    fns = {"bound": _compute_only_runner(pt), "traced": traced}
    fns.update({str(d): at_depth(d) for d in DEPTHS})
    best = time_interleaved(fns, rounds=5, warmup=1)
    lower_bound = best["bound"] * 1e3
    print(f"  compute-only lower bound {lower_bound:8.2f} ms "
          f"({num_partitions} resident partitions)")

    sweep = {}
    for depth in DEPTHS:
        ms = best[str(depth)] * 1e3
        st = stats_by_depth[depth]
        sweep[str(depth)] = {
            "wall_ms": round(ms, 3),
            "overlap_efficiency": round(lower_bound / ms, 4),
            "h2d_ms": st["h2d_ms"],
            "compute_ms": st["compute_ms"],
            "merge_ms": st["merge_ms"],
            "inflight_bytes_max": st["inflight_bytes_max"],
        }
        print(f"  depth {depth} | wall {ms:8.2f} ms | "
              f"overlap {lower_bound / ms:6.1%} | "
              f"h2d {st['h2d_ms']:7.1f} ms | merge {st['merge_ms']:6.1f} ms")

    # telemetry overhead (DESIGN.md §14): traced wall over trace-off wall
    # at the default depth, minus one. CI asserts < 2%.
    telemetry_overhead = best["traced"] / best[str(DEFAULT_DEPTH)] - 1.0
    print(f"  telemetry overhead (trace ON vs OFF, depth {DEFAULT_DEPTH}): "
          f"{telemetry_overhead:+.2%}")

    # EXPLAIN ANALYZE reconciliation: the analyzed run's self-reported
    # movement must match an independent count_h2d recording of the same
    # query exactly — partitions executed, transfer count AND bytes.
    with dispatch.overrides(prefetch_depth=DEFAULT_DEPTH):
        q.explain_analyze()
        la = q.last_analysis
        moved = []
        with count_h2d(moved):
            q.run()
    reconciled = (la["executed"] == q.last_stats["executed"]
                  and la["transferred"] == la["transfers_seen"] == len(moved)
                  and la["bytes_moved"] == sum(moved))
    print(f"  explain_analyze: {la['executed']} executed, "
          f"{la['transfers_seen']} transfers, {la['bytes_moved']} bytes "
          f"({'reconciled' if reconciled else 'MISMATCH vs count_h2d'})")

    report = {
        "bench": "stream_overlap",
        **device_info(),
        "rows": n,
        "num_partitions": num_partitions,
        "compute_only_ms": round(lower_bound, 3),
        "depths": sweep,
        # CI-gated headline: overlap realized at the default depth
        "overlap_efficiency": sweep[str(DEFAULT_DEPTH)]["overlap_efficiency"],
        "depth0_gap": round(
            sweep["0"]["wall_ms"]
            / sweep[str(DEFAULT_DEPTH)]["wall_ms"], 3),
        # CI-gated (< 0.02): tracing must stay in the noise
        "telemetry_overhead": round(telemetry_overhead, 4),
        "explain_analyze": {
            "reconciled": reconciled,
            "executed": la["executed"],
            "pruned": la["pruned"],
            "transfers_seen": la["transfers_seen"],
            "bytes_moved": la["bytes_moved"],
            "bytes_total": la["bytes_total"],
            "wall_ms": la["wall_ms"],
        },
    }
    os.makedirs(ART_DIR, exist_ok=True)
    path = os.path.join(ART_DIR, out_name)
    with open(path, "w") as f:
        json.dump(report, f, indent=1)
    print(f"[bench_stream] overlap efficiency "
          f"{report['overlap_efficiency']:.1%} at depth {DEFAULT_DEPTH} "
          f"(depth-0 gap {report['depth0_gap']:.2f}x) -> {path}")
    return report


if __name__ == "__main__":
    run()


def chaos(n=1_000_000, num_partitions=16, seed=11,
          out_name="BENCH_faults.json", trace_name="TRACE_faults.json"):
    """Seeded-fault recovery pass (DESIGN.md §15): the chaos CI gate.

    Ingest-validates the table, then runs the streamed group-by query
    under a SEEDED FaultPlan — 3 transient transfer faults + 1 device
    OOM, each at attempt 0 of a distinct partition — and asserts the
    recovery contract end to end:

      * the result is BIT-IDENTICAL to the clean run (retry re-issues the
        copy; depth degradation resumes the fold from the failed
        partition with the accumulator intact);
      * every injected fault is visible (3 retries, >=1 depth
        degradation in ``last_stats`` and the always-on fault counters);
      * recovery is cheap: faulted wall / clean wall <= 1.5 (CI-gated).

    A second identically-seeded plan re-runs with tracing ON to export
    the fault-event timeline (``TRACE_faults.json``: injections, retries
    and degradations as instants on the dedicated ``fault`` track).
    """
    import time

    from repro.core.faults import FaultPlan

    rng = np.random.default_rng(7)
    data = make_dict_heavy(rng, n)
    cfg = compress.CompressionConfig(plain_threshold=1000)
    pt = PartitionedTable.from_arrays(data, cfg=cfg,
                                      num_partitions=num_partitions,
                                      pack=True)
    pt.validate()  # integrity gate: corrupted ingest fails the bench here
    q = _query(pt)
    q.run()  # trace + compile once; both timed passes below are warm

    def payload(r):
        ng = int(r.num_groups)
        out = {f"k:{g}": np.asarray(r.keys[g])[:ng] for g in r.keys}
        out.update({f"a:{o}": np.asarray(r.aggs[o])[:ng] for o in r.aggs})
        return out

    def timed():
        t0 = time.perf_counter()
        out = payload(q.run())
        return out, (time.perf_counter() - t0) * 1e3

    clean, clean_ms = min((timed() for _ in range(3)), key=lambda x: x[1])

    plan = FaultPlan.seeded(seed, parts=num_partitions, transients=3,
                            ooms=1, oom_site="compute")
    with plan:
        faulted, faulted_ms = timed()
    st = dict(q.last_stats)
    identical = (set(clean) == set(faulted)
                 and all(np.array_equal(clean[k], faulted[k])
                         for k in clean))
    wall_ratio = faulted_ms / clean_ms

    # identically-seeded second plan, tracing ON: capture the fault-event
    # timeline (plan attempt counters are plan-scoped, so the same
    # schedule re-fires here)
    telemetry.reset()
    with dispatch.overrides(enable_trace=True):
        with FaultPlan.seeded(seed, parts=num_partitions, transients=3,
                              ooms=1, oom_site="compute"):
            q.run()
    counters = {k: v for k, v in telemetry.registry().counters().items()
                if k.startswith("fault.")}
    os.makedirs(ART_DIR, exist_ok=True)
    trace_path = telemetry.export_chrome_trace(
        os.path.join(ART_DIR, trace_name))

    report = {
        "bench": "fault_recovery",
        **device_info(),
        "rows": n,
        "num_partitions": num_partitions,
        "seed": seed,
        "scheduled": [[f.site, f.part, f.attempt, f.kind]
                      for f in plan.scheduled()],
        "fired": len(plan.fired),
        # CI-gated: recovery must be exact and visible
        "identical": bool(identical),
        "retries": st.get("retries", 0),
        "degradations": st.get("degradations", 0),
        "final_prefetch_depth": st.get("prefetch_depth", 0),
        # CI-gated: recovery must be cheap (<= 1.5x the clean wall)
        "clean_wall_ms": round(clean_ms, 3),
        "faulted_wall_ms": round(faulted_ms, 3),
        "wall_ratio": round(wall_ratio, 4),
        "fault_counters": counters,
        "trace": trace_path,
    }
    path = os.path.join(ART_DIR, out_name)
    with open(path, "w") as f:
        json.dump(report, f, indent=1)
    print(f"[bench_stream.chaos] {len(plan.fired)} faults fired | "
          f"identical={identical} | {report['retries']} retries, "
          f"{report['degradations']} degradations | "
          f"wall {clean_ms:.1f} -> {faulted_ms:.1f} ms "
          f"({wall_ratio:.2f}x) -> {path}")
    return report
