"""The join layer's per-layer reader ``join.prepare_share`` (the host's
join-side preparation over the window), on made-up runs and on a small
CPU run of ``ssb-stream-scan``, with the cell's probe route and its
zone-map reading."""
from types import SimpleNamespace

import pytest

import _paths  # noqa: F401
import harness

SMALL = {"scale_factor": 0.002, "partition_rows": 1 << 12}
PREPARE = harness.metric_reader("join.prepare_share")
PRUNE = harness.metric_reader("prune.skipped_share")


def _run(counters=None, stats=(), window_s=2.0):
    return SimpleNamespace(counters=counters or {}, window_s=window_s,
                           records=[{"stats": s} for s in stats])


def test_prepare_share_on_made_up_stats():
    run = _run(stats=[{"join_prep_ms": 100.0}, {"join_prep_ms": 300.0},
                      {"h2d_ms": 5.0}], window_s=4.0)
    assert PREPARE(run) == pytest.approx(10.0)


@pytest.mark.parametrize("stats", [[], [{"h2d_ms": 1.0}]])
def test_prepare_share_reads_nothing_from_a_program_without_it(stats):
    assert PREPARE(_run(stats=stats)) is None


def test_readers_on_a_small_cpu_run_of_the_ssb_cell(monkeypatch):
    """All 13 templates' joins take the direct map; preparation is a
    positive share of the window, below all of it; the zone maps' share
    of skipped partitions reads (at this size, partitions of 2^12 rows,
    some do skip)."""
    runs = []
    real = harness.stream_window

    def keep(run, *a, **k):
        runs.append(run)
        return real(run, *a, **k)

    monkeypatch.setattr(harness, "stream_window", keep)
    out = harness.run_cell("ssb-stream-scan", 4_000_000_003, 1, False,
                           require_chip=False, overrides=SMALL,
                           log=lambda msg: None)
    assert out["correct"], out["checks"]
    (run,) = runs
    assert len(run.records) % 13 == 0 and run.records
    joins = {name: len(spec["dims"])  # one join per dimension
             for name, spec in run.cell.module.QUERIES.items()}
    assert run.counters["join.probe.direct"] == sum(
        joins[r["template"]] for r in run.records)
    assert run.counters.get("join.probe.sorted", 0) == 0
    assert 0 <= PRUNE(run) < 100
    assert 0 < PREPARE(run) < 100
