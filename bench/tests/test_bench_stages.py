"""Device idle time attributed to the engine's stages (``bench/stages.py``)
on made-up traces, on the recorded TPU v5e traces and on a CPU profile of
the engine, and the per-layer readers built on it."""
import gzip
import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

import _paths  # noqa: F401
import harness
import stages
import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
IDLE_IN = ["device.idle_in." + s for s in (
    "prepare", "h2d_issue", "h2d_wait", "dispatch", "block", "d2h", "fold",
    "finalize", "untraced")]


def _load(name):
    with gzip.open(os.path.join(DATA, name), "rt") as f:
        return json.load(f)


def _made_up():
    # window [0, 100); the device runs [10, 20) and [60, 70)
    return {"host": [["bench:window", 0, 100], ["bench:query:q1", 0, 90]],
            "devices": [{"ops": [["fusion", 10, 10], ["fusion", 60, 10]],
                         "modules": []}],
            "query_thread": 1,
            "spans": [["repro:query", 5, 85, 1],
                      ["repro:prepare", 5, 10, 1],  # idle [5, 10)
                      ["repro:fold", 20, 30, 1],  # idle [20, 50) less d2h
                      ["repro:d2h", 25, 5, 1],  # idle [25, 30)
                      ["repro:transfer", 0, 100, 2],  # another thread
                      ["repro:finalize", 80, 5, 1]]}  # idle [80, 85)


def test_idle_goes_to_the_innermost_stage_of_the_query_thread():
    ex = _made_up()
    by = stages.idle_by_stage(ex)
    r = trace.reduce(ex)
    assert by == pytest.approx({
        "prepare": 5e-9, "fold": 25e-9, "d2h": 5e-9, "finalize": 5e-9,
        # [0, 5) outside every span; [50, 60), [70, 80), [85, 100) under
        # the query root alone or no span
        "untraced": 40e-9})
    assert "transfer" not in by  # the other thread never attributes
    assert sum(by.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_spans_that_share_a_start_attribute_to_the_shorter():
    ex = _made_up()
    ex["spans"] = [["repro:d2h", 20, 25, 1], ["repro:fold", 20, 30, 1]]
    by = stages.idle_by_stage(ex)
    assert by["d2h"] == pytest.approx(25e-9)  # [20, 45)
    assert by["fold"] == pytest.approx(5e-9)  # [45, 50)


def test_a_trace_without_spans_is_all_untraced():
    for ex in (_made_up(), _load("tpu_v5e_stream.json.gz")):
        ex.pop("spans", None)
        r = trace.reduce(ex)
        by = stages.idle_by_stage(ex)
        assert list(by) == ["untraced"]
        assert by["untraced"] == pytest.approx(r["window_s"] - r["busy_s"])


def test_recorded_tpu_trace_attributes_nearly_all_idle_time():
    ex = _load("tpu_v5e_stream_spans.json.gz")
    r = trace.reduce(ex)
    by = stages.idle_by_stage(ex)
    idle = 1.0 - r["busy_s"] / r["window_s"]
    assert sum(by.values()) / r["window_s"] == pytest.approx(idle)
    assert by["untraced"] / r["window_s"] < 0.02
    assert {"h2d_wait", "dispatch", "block", "fold", "d2h"} <= set(by)


def _run(trace_out, records=(), counters=None):
    return SimpleNamespace(trace=trace_out, records=list(records),
                           window_s=trace_out["window_s"] if trace_out else 0,
                           counters=counters or {})


@pytest.mark.parametrize("fixture", ["made_up", "tpu_v5e_stream_spans"])
def test_idle_in_readers_sum_to_the_idle_share(fixture):
    ex = _made_up() if fixture == "made_up" else _load(fixture + ".json.gz")
    r = trace.reduce(ex)
    r["idle_by_stage"] = stages.idle_by_stage(ex)
    run = _run(r)
    shares = [harness.metric_reader(n)(run) for n in IDLE_IN]
    idle = harness.metric_reader("device.idle_share.stream")(run)
    assert all(s is not None and s >= 0 for s in shares)
    assert sum(shares) == pytest.approx(idle)


def test_readers_report_nothing_for_a_program_without_the_stages():
    ex = _load("tpu_v5e_stream.json.gz")
    r = trace.reduce(ex)
    for run in (_run(r), _run(dict(r, idle_by_stage=
                                   stages.idle_by_stage(ex)))):
        assert [harness.metric_reader(n)(run) for n in IDLE_IN] == \
            [None] * len(IDLE_IN)
    old = _run(r, records=[{"stats": {"merge_ms": 3.0}}])
    assert harness.metric_reader("d2h.share")(old) is None
    assert harness.metric_reader("plan.programs_traced")(old) is None
    new = _run(r, records=[{"stats": {"merge_ms": 3.0, "d2h_ms": 1.5}}],
               counters={"programs_traced": 0})
    assert harness.metric_reader("d2h.share")(new) == pytest.approx(
        100.0 * 1.5e-3 / r["window_s"])
    assert harness.metric_reader("plan.programs_traced")(new) == 0


def test_host_spans_of_a_profiled_engine_run(tmp_path):
    """On the CPU: the engine's stages and the client's query annotation
    come back on one host line; the copies on another."""
    import jax
    from jax.profiler import ProfileData

    from repro.core import compress
    from repro.core.partition import PartitionedQuery, PartitionedTable
    from repro.core.plan import col
    from repro.kernels import dispatch

    rng = np.random.default_rng(7)
    n = 20_000
    pt = PartitionedTable.from_arrays(
        {"k": np.sort(rng.integers(0, 100, n)).astype(np.int32),
         "v": rng.integers(0, 50, n).astype(np.int32)},
        cfg=compress.CompressionConfig(), num_partitions=4)
    q = (PartitionedQuery(pt).filter(col("k") < 60)
         .aggregate({"s": ("sum", "v")}))
    q.run()
    with dispatch.overrides(enable_trace=True, prefetch_depth=2):
        jax.profiler.start_trace(str(tmp_path))
        try:
            with jax.profiler.TraceAnnotation("bench:query:q"):
                q.run()
        finally:
            jax.profiler.stop_trace()
    (path,) = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
               for f in fs if f.endswith(".xplane.pb")]
    got = stages.host_spans(ProfileData.from_file(path))
    thread = got["query_thread"]
    assert thread is not None
    mine = {s[0] for s in got["spans"] if s[3] == thread}
    assert {"repro:query", "repro:prepare", "repro:prune", "repro:h2d_issue",
            "repro:h2d_wait", "repro:dispatch", "repro:block", "repro:fold",
            "repro:d2h", "repro:finalize"} <= mine
    assert {s[0] for s in got["spans"] if s[3] != thread} == \
        {"repro:transfer"}
