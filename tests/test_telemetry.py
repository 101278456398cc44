"""Engine-wide telemetry (DESIGN.md §14, core/telemetry.py).

Five layers:

  1. registry units — span/instant/counter recording, the bounded event
     ring (oldest-drop + ``dropped_events``), Chrome trace-event export
     validity, and the disabled path returning the shared no-op span;
  2. StreamStats completeness — ``as_dict`` is generic over the dataclass
     fields, so a populated field can never again be silently dropped
     (the seed's as_dict omitted ``executed`` from every bench JSON);
  3. EXPLAIN / EXPLAIN ANALYZE — the compressed-domain plan tree renders
     encodings, chosen paths and the zone-map visit estimate; the
     analyzed run's movement report reconciles EXACTLY with
     ``last_stats`` and the transfer fixture;
  4. wiring — per-partition executor spans, zone-map verdicts with the
     responsible predicate bound, dispatch routing records, and the
     always-on H2D counters behind ``count_h2d`` / ``transfer_counter``;
  5. concurrency + cost — traced concurrent serving reconciles per-query
     attribution with ticket stats, and what tracing costs is counted:
     untraced, no event and no annotation; traced, a fixed number of
     events per executed partition;
  6. the profiler's clock — under ``jax.profiler`` the engine's stages
     land as ``repro:`` host annotations beside the device's operations,
     nested as they ran, with the same durations as ``StreamStats``.
"""
import collections
import dataclasses
import glob
import json
import os
import sys
import threading

import jax
import numpy as np
import pytest

from repro.core import compress, stream, telemetry
from repro.core.partition import (
    PartitionedQuery,
    PartitionedTable,
    partition_match_verdict,
)
from repro.core.plan import Query, col
from repro.core.serve import QueryServer
from repro.core.table import Table
from repro.kernels import dispatch

CFG = compress.CompressionConfig(plain_threshold=1000)


def _clustered_pt(rng, n=24_000, parts=8):
    """qty-clustered partitioned table: zone maps are selective."""
    data = {
        "qty": np.sort(rng.integers(0, 1000, n)).astype(np.int32),
        "units": rng.integers(0, 100, n).astype(np.int32),
        "region": rng.integers(0, 5, n).astype(np.int32),
    }
    return PartitionedTable.from_arrays(data, cfg=CFG, num_partitions=parts)


# ---------------------------------------------------------------------------
# 1. registry units
# ---------------------------------------------------------------------------


def test_span_records_only_when_enabled():
    telemetry.reset()
    with telemetry.span("cold", "device", qid=7):
        pass
    assert telemetry.registry().events(name="cold") == []  # default: off
    # and the disabled path hands back ONE shared no-op object
    assert telemetry.span("a") is telemetry.span("b")

    with dispatch.overrides(enable_trace=True):
        with telemetry.span("hot", "device", qid=7, part=3):
            pass
        telemetry.instant("mark", "main", qid=7)
    (ev,) = telemetry.registry().events(name="hot")
    assert ev["track"] == "device"
    assert ev["dur"] > 0
    assert ev["attrs"] == {"qid": 7, "part": 3}
    (mk,) = telemetry.registry().events(name="mark")
    assert mk["dur"] == 0.0
    # query_trace filters on the qid attr
    assert {e["name"] for e in telemetry.query_trace(7)} == {"hot", "mark"}


def test_counters_accumulate_and_reset():
    telemetry.reset()
    telemetry.add_counter("x")
    telemetry.add_counter("x", 4)
    assert telemetry.registry().counter("x") == 5
    assert telemetry.registry().counters()["x"] == 5
    telemetry.reset()
    assert telemetry.registry().counter("x") == 0


def test_event_ring_drops_oldest_and_counts():
    telemetry.reset()
    with dispatch.overrides(enable_trace=True, trace_buffer_events=16):
        for i in range(40):
            telemetry.instant("e", seq=i)
        evs = telemetry.registry().events(name="e")
        assert len(evs) == 16
        # OLDEST events dropped: the survivors are the most recent 16
        assert [e["attrs"]["seq"] for e in evs] == list(range(24, 40))
        assert telemetry.registry().dropped == 24
        assert telemetry.registry().counter("dropped_events") == 24


def test_chrome_trace_export(tmp_path):
    telemetry.reset()
    with dispatch.overrides(enable_trace=True):
        with telemetry.span("work", "device", qid=1):
            pass
        telemetry.instant("mark", "transfer", bytes=64, skipped=None)
    path = telemetry.export_chrome_trace(str(tmp_path / "trace.json"))
    with open(path) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    names = {e["args"]["name"] for e in meta if e["name"] == "thread_name"}
    assert set(telemetry.TRACKS) <= names  # one named row per track
    (x,) = [e for e in evs if e["ph"] == "X"]
    assert x["name"] == "work" and x["dur"] > 0 and x["ts"] >= 0
    (i,) = [e for e in evs if e["ph"] == "i"]
    assert i["s"] == "t"
    assert i["args"] == {"bytes": 64}  # None-valued attrs filtered
    assert doc["displayTimeUnit"] == "ms"


def test_env_knobs():
    p = dispatch.policy_from_env({"REPRO_TRACE": "1",
                                  "REPRO_TRACE_BUFFER": "128"})
    assert p.enable_trace is True
    assert p.trace_buffer_events == 128
    assert dispatch.policy_from_env({"REPRO_TRACE": "0"}).enable_trace is False
    assert dispatch.policy_from_env({}).enable_trace is False  # auto -> off


# ---------------------------------------------------------------------------
# 2. StreamStats completeness
# ---------------------------------------------------------------------------


def test_streamstats_as_dict_is_field_complete():
    st = stream.StreamStats()
    # populate EVERY field non-default so a dropped key is detectable
    for i, f in enumerate(dataclasses.fields(stream.StreamStats)):
        setattr(st, f.name, i + 1)
    d = st.as_dict()
    assert set(d) == {f.name for f in dataclasses.fields(stream.StreamStats)}
    assert d["executed"] == [f.name for f in
                             dataclasses.fields(stream.StreamStats)
                             ].index("executed") + 1


# ---------------------------------------------------------------------------
# 3. EXPLAIN / EXPLAIN ANALYZE
# ---------------------------------------------------------------------------


def test_explain_renders_plan_and_estimate(rng):
    pt = _clustered_pt(rng)
    q = (PartitionedQuery(pt).filter(col("qty") < 250)
         .groupby(["region"], {"s": ("sum", "units")}, num_groups_cap=8))
    text = q.explain()
    assert f"qid={q.qid}" in text
    assert "filter qty lt 250" in text
    assert "groupby[region]" in text
    assert "sort-free scatter" in text  # the chosen grouping path
    assert "estimated partitions:" in text
    # the estimate matches the zone-map verdicts exactly (host-static)
    est = sum(partition_match_verdict(p, q.ops, pt)[0]
              for p in pt.partitions)
    assert f"visit {est} / skip {len(pt.partitions) - est}" in text


def test_explain_analyze_reconciles_with_stats(rng, transfer_counter):
    pt = _clustered_pt(rng)
    q = (PartitionedQuery(pt).filter(col("qty") < 250)
         .aggregate({"s": ("sum", "units"), "c": ("count", None)}))
    text = q.explain_analyze()
    la = q.last_analysis
    # exact reconciliation with the engine's own accounting
    assert la["executed"] == q.last_stats["executed"]
    assert la["pruned"] == q.last_stats["skipped"]
    assert la["transferred"] == q.last_stats["transferred"]
    # ... and with the independent transfer fixture (same analyzed run)
    assert la["transfers_seen"] == len(transfer_counter)
    assert la["bytes_moved"] <= la["bytes_total"] == pt.nbytes()
    assert "actual: wall" in text
    assert f"{la['executed']} executed" in text
    # zone-pruned partitions name the responsible predicate bound
    assert la["pruned"] > 0
    assert any("qty lt 250 outside zone" in c for c in la["pruned_by"])


def test_explain_analyze_resident_table(rng):
    t = Table.from_arrays({"v": rng.integers(0, 50, 3000).astype(np.int32)},
                          cfg=CFG)
    q = Query(t).filter(col("v") >= 10).aggregate({"c": ("count", None)})
    text = q.explain_analyze()
    assert "actual: wall" in text
    assert q.last_analysis["wall_ms"] >= 0
    # plan-only explain shows the encoding the filter runs against
    assert "filter v ge 10" in q.explain()


def test_explain_analyze_leaves_trace_policy_off(rng):
    pt = _clustered_pt(rng)
    q = (PartitionedQuery(pt).filter(col("qty") < 250)
         .aggregate({"c": ("count", None)}))
    q.explain_analyze()
    assert dispatch.policy().enable_trace is False


# ---------------------------------------------------------------------------
# 4. wiring: executor spans, zone verdicts, routing, H2D counters
# ---------------------------------------------------------------------------


def test_streamed_run_emits_qid_tagged_spans(rng):
    pt = _clustered_pt(rng)
    q = (PartitionedQuery(pt).filter(col("qty") < 250)
         .aggregate({"s": ("sum", "units")}))
    telemetry.reset()
    with dispatch.overrides(enable_trace=True):
        q.run()
    tr = telemetry.query_trace(q.qid)
    names = {e["name"] for e in tr}
    assert {"query", "prepare", "prune", "transfer", "program", "fold",
            "d2h", "finalize"} <= names
    # one program span per executed partition, labelled with its index
    progs = [e for e in tr if e["name"] == "program"]
    assert len(progs) == q.last_stats["executed"]
    assert all(isinstance(e["attrs"].get("part"), int) for e in progs)
    # each transfer span carries the bytes it shipped
    xfers = [e for e in tr if e["name"] == "transfer"]
    assert len(xfers) == q.last_stats["transferred"]
    assert all(e["attrs"]["bytes"] > 0 for e in xfers)
    # the prune span counts the verdicts; skips carry a cause
    (pr,) = [e for e in tr if e["name"] == "prune"]
    assert pr["attrs"]["visit"] == q.last_stats["executed"]
    assert pr["attrs"]["skip"] == q.last_stats["skipped"] > 0
    skips = [v for v in q.last_verdicts if not v[1]]
    assert len(skips) == q.last_stats["skipped"]
    assert all("outside zone" in cause for _, _, cause in skips)
    # the verdicts are no longer one event each
    assert "zone_map" not in names and "h2d" not in names


def test_route_records_mark_compilations(rng):
    telemetry.reset()
    vals = np.arange(64, dtype=np.int32)
    segs = np.zeros(64, dtype=np.int32)
    with dispatch.overrides(enable_trace=True):
        dispatch.segment_sum(np.asarray(vals), np.asarray(segs), 1)
    reg = telemetry.registry()
    routed = [k for k in reg.counters() if k.startswith("route.segment_sum.")]
    assert len(routed) == 1 and reg.counter(routed[0]) == 1
    (ev,) = reg.events(name="route.segment_sum")
    assert ev["attrs"]["path"] in ("kernel", "xla_scatter")
    assert ev["attrs"]["reason"]


def test_h2d_counters_always_on(rng):
    pt = _clustered_pt(rng, n=6000, parts=4)
    q = PartitionedQuery(pt).aggregate({"c": ("count", None)})
    telemetry.reset()
    q.run()  # tracing OFF — the transfer counters must book anyway
    reg = telemetry.registry()
    assert reg.counter("h2d_calls") == q.last_stats["transferred"] == 4
    assert reg.counter("h2d_bytes") > 0
    assert reg.events() == []  # but no events were recorded


def test_h2d_listener_scoped(rng):
    pt = _clustered_pt(rng, n=6000, parts=4)
    q = PartitionedQuery(pt).aggregate({"c": ("count", None)})
    seen = []
    with telemetry.h2d_listener(lambda nbytes, tree: seen.append(nbytes)):
        q.run()
    assert len(seen) == 4 and all(b > 0 for b in seen)
    before = len(seen)
    q.run()  # outside the scope: the listener is unhooked
    assert len(seen) == before


# ---------------------------------------------------------------------------
# 5. concurrency + cost
# ---------------------------------------------------------------------------


def test_traced_concurrent_serving_reconciles(rng):
    pt = _clustered_pt(rng)

    def mk_queries():
        return [
            (PartitionedQuery(pt).filter(col("qty") < 250)
             .aggregate({"s": ("sum", "units"), "c": ("count", None)})),
            (PartitionedQuery(pt).filter(col("qty") < 250)
             .groupby(["region"], {"s": ("sum", "units")},
                      num_groups_cap=8)),
            (PartitionedQuery(pt).filter(col("qty") >= 750)
             .aggregate({"m": ("max", "units")})),
        ]

    telemetry.reset()
    results = [None, None]
    with dispatch.overrides(enable_trace=True):
        with QueryServer(pt) as srv:
            def client(slot):
                qs = mk_queries()
                tickets = [srv.submit(q) for q in qs]
                for t in tickets:
                    srv.result(t, timeout=120)
                results[slot] = (qs, tickets)

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(2)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()

    total_transferred = 0
    for qs, tickets in results:
        for q, t in zip(qs, tickets):
            assert t.error is None
            st = t.stats
            total_transferred += st.get("transferred", 0)
            tr = telemetry.query_trace(q.qid)
            progs = [e for e in tr if e["name"] == "serve.program"]
            # per-query attribution: span count == executed, and the
            # span-level source tags sum to the ticket's own attribution
            assert len(progs) == st["executed"]
            srcs = {}
            for e in progs:
                srcs[e["attrs"]["src"]] = srcs.get(e["attrs"]["src"], 0) + 1
            assert srcs.get("miss", 0) == st.get("transferred", 0)
            assert srcs.get("lru", 0) == st.get("lru_hits", 0)
            assert srcs.get("shared", 0) == st.get("shared_hits", 0)
    # and across the whole run: tickets' transfers == actual device_puts
    assert total_transferred == telemetry.registry().counter("h2d_calls")


PER_PARTITION = ("transfer", "h2d_wait", "dispatch", "block", "program",
                 "fold", "d2h")
PER_QUERY = ("query", "prepare", "prune", "finalize")


def _counting_annotations(monkeypatch):
    made = []

    class Counting(telemetry.TraceAnnotation):
        def __init__(self, name, **kwargs):
            made.append(name)
            super().__init__(name, **kwargs)

    monkeypatch.setattr(telemetry, "TraceAnnotation", Counting)
    return made


@pytest.mark.parametrize("traced", [False, True], ids=["off", "on"])
def test_trace_overhead_within_noise(rng, monkeypatch, traced):
    """What tracing costs on the depth-2 streamed path, counted rather
    than timed (its wall-clock cost is measured on the chip, PERF.md).

    Off: the run records no ring event, constructs no profiler
    annotation, and ``span()`` is the shared no-op. On: the ring holds a
    fixed number of events per executed partition plus a fixed number
    per query, and every span but the ring-only ``program`` opens one
    annotation."""
    pt = _clustered_pt(rng, n=60_000, parts=8)
    q = (PartitionedQuery(pt).filter(col("qty") < 750)
         .groupby(["region"], {"s": ("sum", "units")}, num_groups_cap=8))
    q.run()  # compile once
    made = _counting_annotations(monkeypatch)
    telemetry.reset()
    with dispatch.overrides(prefetch_depth=2, enable_trace=traced):
        assert (telemetry.span("a") is telemetry.span("b")) is not traced
        q.run()
    evs = telemetry.registry().events()
    if not traced:
        assert evs == [] and made == []
        return
    executed = q.last_stats["executed"]
    assert 0 < executed < len(pt.partitions)
    counts = collections.Counter(e["name"] for e in evs)
    expected = dict.fromkeys(PER_PARTITION, executed)
    expected.update(dict.fromkeys(PER_QUERY, 1))
    # the ring's fill, a top-up after each fold, the pool's shutdown
    expected["h2d_issue"] = executed + 2
    assert counts == expected
    assert len(made) == len(evs) - executed
    assert set(made) == {telemetry.ANNOTATION_PREFIX + n
                         for n in expected if n != "program"}


def test_serving_stats_unchanged_when_disabled(rng):
    """Tracing off (the default) must not change serving results or the
    stats schema — the instrumentation is observation only."""
    pt = _clustered_pt(rng, n=6000, parts=4)
    q1 = (PartitionedQuery(pt).filter(col("qty") < 500)
          .aggregate({"s": ("sum", "units")}))
    q2 = (PartitionedQuery(pt).filter(col("qty") < 500)
          .aggregate({"s": ("sum", "units")}))
    solo = q1.run()
    with QueryServer(pt) as srv:
        t = srv.submit(q2)
        served = srv.result(t, timeout=120)
    np.testing.assert_array_equal(np.asarray(solo["s"]),
                                  np.asarray(served["s"]))
    assert {"executed", "skipped", "transferred"} <= set(t.stats)


# ---------------------------------------------------------------------------
# 6. the profiler's clock
# ---------------------------------------------------------------------------


def _annotations(logdir):
    """``repro:`` host events of the one ``.xplane.pb`` under ``logdir``
    as (name, start_ns, end_ns, thread, stats)."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for j, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(telemetry.ANNOTATION_PREFIX):
                    out.append((ev.name[len(telemetry.ANNOTATION_PREFIX):],
                                ev.start_ns, ev.start_ns + ev.duration_ns,
                                (plane.name, j), dict(ev.stats)))
    return out


def _profiled_pt(rng, n=400_000, parts=8):
    """Many groups, so each partition's fold and fetch take long enough
    (hundreds of microseconds on the CPU) that an annotation's own
    microsecond stays under 1% of them."""
    data = {
        "qty": np.sort(rng.integers(0, 1000, n)).astype(np.int32),
        "units": rng.integers(0, 100, n).astype(np.int32),
        "price": rng.random(n).astype(np.float32),
        "region": rng.integers(0, 5, n).astype(np.int32),
    }
    return PartitionedTable.from_arrays(data, cfg=CFG, num_partitions=parts)


@pytest.mark.parametrize("terminal", ["groupby", "aggregate"])
def test_profiler_trace_holds_the_engine_stages(rng, tmp_path, terminal):
    pt = _profiled_pt(rng)
    q = PartitionedQuery(pt).filter(col("qty") < 750)
    if terminal == "groupby":
        q = q.groupby(["qty", "region"], {"s": ("sum", "units"),
                                          "p": ("avg", "price")},
                      num_groups_cap=8192)
    else:
        q = q.aggregate({f"{agg}_{c}": (agg, c)
                         for agg in ("sum", "min", "max", "avg")
                         for c in ("qty", "units", "price")})
    q.run()  # compile outside the profiled run
    telemetry.reset()
    # a span opens its annotation, then reads the clock; a thread switch
    # to the transfer thread in between would widen the annotation by up
    # to a switch interval, so the client keeps the interpreter until it
    # waits on the device or the transfer thread
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1.0)
    try:
        with dispatch.overrides(enable_trace=True, prefetch_depth=2):
            jax.profiler.start_trace(str(tmp_path))
            try:
                q.run()
            finally:
                jax.profiler.stop_trace()
    finally:
        sys.setswitchinterval(interval)
    st = q.last_stats
    spans = [s for s in _annotations(str(tmp_path))
             if s[4].get("qid") == q.qid]
    (root,) = [s for s in spans if s[0] == "query"]
    main = [s for s in spans if s[3] == root[3]]
    assert all(root[1] <= s[1] and s[2] <= root[2] for s in main)
    names = {s[0] for s in main}
    assert {"prepare", "prune", "h2d_wait", "dispatch", "block", "fold",
            "d2h", "finalize"} <= names
    # the copies run on the transfer thread, not under the client's stages
    assert {s[0] for s in spans if s[3] != root[3]} == {"transfer"}
    folds = [s for s in main if s[0] == "fold"]
    d2hs = [s for s in main if s[0] == "d2h"]
    assert len(folds) == len(d2hs) == st["executed"] > 0
    for d in d2hs:  # each fetch inside a fold
        assert any(f[1] <= d[1] and d[2] <= f[2] for f in folds)
    (prune,) = [s for s in main if s[0] == "prune"]
    assert prune[4]["visit"] == st["executed"]
    assert prune[4]["skip"] == st["skipped"]

    def ms(group):
        return sum(e - s for _, s, e, _, _ in group) / 1e6

    assert ms(folds) == pytest.approx(st["merge_ms"], rel=0.01)
    assert ms(d2hs) == pytest.approx(st["d2h_ms"], rel=0.01)
    # the ring's parent ids form one tree rooted at the query span
    ring = telemetry.query_trace(q.qid)
    ids = {e["id"] for e in ring}
    (top,) = [e for e in ring if e["name"] == "query"]
    assert top["parent"] is None
    assert all(e["parent"] in ids for e in ring if e is not top)
    by_id = {e["id"]: e for e in ring}
    assert all(by_id[e["parent"]]["name"] == "fold"
               for e in ring if e["name"] == "d2h")


def test_programs_traced_counts_jit_traces(rng):
    pt = _clustered_pt(rng, n=6000, parts=4)
    q = (PartitionedQuery(pt).filter(col("qty") < 500)
         .aggregate({"s": ("sum", "units")}))
    reg = telemetry.registry()
    before = reg.counter("programs_traced")
    q.run()  # tracing off: the counter is always on
    first = reg.counter("programs_traced")
    assert first > before
    q.run()
    assert reg.counter("programs_traced") == first
