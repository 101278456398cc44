"""The harness end to end on the CPU at a tiny size: discovery by name,
the refusal to run without a chip, the traffic generator, and ``correct``
turning false when the timed path is broken underneath."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import _paths  # noqa: F401
import harness
import traffic

SMALL = {"scale_factor": 0.002, "partition_rows": 1 << 12}
BENCH = harness.load_benchmark()

# A cell whose files are under bench/ but whose entries are not in
# BENCHMARK.json yet (PERF.md, Open questions). The tests drive it from a
# root that has the entries too.
PLANNED = {
    "configs": [{"name": "ssb-sf2", "file": "bench/configs/ssb-sf2.json"}],
    "workloads": [{"name": "ssb-serve-resident", "config": "ssb-sf2",
                   "traffic": "serve-open-zipf", "chips": 1}],
    "end_to_end": [
        {"name": "served_p50_ms", "unit": "ms",
         "workloads": ["ssb-serve-resident"]},
        {"name": "served_p95_ms", "unit": "ms",
         "workloads": ["ssb-serve-resident"]}],
    "per_layer": [
        {"name": name, "unit": "%", "moves": "served_p95_ms",
         "workloads": ["ssb-serve-resident"]}
        for name in ("fold.share.serve", "serve.residency_hit_rate",
                     "serve.queries_per_pass", "device.idle_share.serve")],
}
ALL = {k: BENCH[k] + PLANNED.get(k, []) if isinstance(BENCH[k], list)
       else BENCH[k] for k in BENCH}
CELLS = [w["name"] for w in ALL["workloads"]]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A root whose BENCHMARK.json holds the planned cells as well."""
    path = tmp_path_factory.mktemp("root")
    os.symlink(harness.BENCH, path / "bench")
    (path / "BENCHMARK.json").write_text(json.dumps(ALL))
    return str(path)


def _run(cell, root, seed=4_000_000_001):
    return harness.run_cell(cell, seed, 1, False, require_chip=False,
                            overrides=SMALL, root=root, log=lambda msg: None)


# -- discovery --------------------------------------------------------------


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_with_its_metrics(cell):
    c = harness.resolve(ALL, cell)
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.per_layer:
        assert callable(harness.metric_reader(m["name"]))
        assert m["moves"] in {e["name"] for e in c.end_to_end}
    for t in c.config["templates"]:
        assert t in c.module.COLUMNS


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    """A later change adds files and entries only; the harness finds them."""
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".traces"))
    bench = json.loads(json.dumps(BENCH))
    src = tmp_path / "bench" / "configs"
    shutil.copy(src / "tpch-sf2-shipdate.json", src / "tpch-new.json")
    shutil.copy(src / "tpch-sf2-shipdate.py", src / "tpch-new.py")
    (tmp_path / "bench" / "mixes" / "stream-new.json").write_text(
        json.dumps({"executor": "stream", "weights": "uniform"}))
    (tmp_path / "bench" / "metrics" / "new.metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    bench["configs"].append(dict(bench["configs"][0], name="tpch-new",
                                 file="bench/configs/tpch-new.json"))
    bench["workloads"].append({"name": "new-cell", "config": "tpch-new",
                               "traffic": "stream-new", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "new.metric", "unit": "%",
                               "better": "higher", "source": "program_counter",
                               "layer": "serve", "moves": "scan_rows_per_s",
                               "workloads": ["new-cell"]})
    for e in bench["end_to_end"]:
        if "workloads" in e and "tpch-stream-scan" in e["workloads"]:
            e["workloads"].append("new-cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    c = harness.resolve(harness.load_benchmark(str(tmp_path)), "new-cell",
                        str(tmp_path))
    assert c.mix["executor"] == "stream"
    assert [m["name"] for m in c.per_layer] == ["new.metric"]
    assert c.config["templates"] == ["q1", "q6"]
    assert harness.metric_reader("new.metric", str(tmp_path))(None) == 42.0


# -- no chip, no result -------------------------------------------------------


def _bench_command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "tpch-stream-scan",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_without_a_chip_exits_nonzero_and_prints_no_result():
    p = _bench_command(harness.ROOT)
    assert p.returncode != 0
    assert "metrics" not in p.stdout and "device" not in p.stdout


def test_run_without_the_program_exits_nonzero(tmp_path):
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".traces"))
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    p = _bench_command(str(tmp_path))
    assert p.returncode != 0
    assert "metrics" not in p.stdout


# -- traffic ------------------------------------------------------------------


def test_schedule_is_poisson_at_the_rate_and_zipf_over_templates():
    names = [f"t{i}" for i in range(13)]
    mix = {"rate_qps": 20.0, "popularity": "zipf", "zipf_exponent": 0.99}
    a = traffic.make_schedule(names, mix, 30, 2 ** 31 + 7)
    assert a == traffic.make_schedule(names, mix, 30, 2 ** 31 + 7)
    assert len(a) == 600
    gaps = np.diff([o for o, _ in a])
    assert abs(a[-1][0] - 30) < 1.0 and abs(gaps.mean() - 1 / 20) < 5e-3
    assert abs(np.median(gaps) - np.log(2) / 20) < 5e-3  # exponential
    counts = sorted((sum(1 for _, n in a if n == t) for t in names),
                    reverse=True)
    want = traffic._counts(traffic.popularity(names, mix), 600)
    assert counts == list(want) and counts[0] > counts[-1]


def test_schedule_draws_ranking_and_order_from_the_seed():
    """Every seed offers the same gaps and the same requests per rank; the
    seed picks the templates' ranks and the order."""
    names = [f"t{i}" for i in range(13)]
    mix = {"rate_qps": 20.0, "popularity": "zipf", "zipf_exponent": 0.99}
    a = traffic.make_schedule(names, mix, 30, 3_000_000_001)
    b = traffic.make_schedule(names, mix, 30, 3_000_000_002)
    assert a != b
    n = len(a)
    quantiles = -np.log1p(-(np.arange(n) + 0.5) / n) / 20.0
    for s in (a, b):
        gaps = np.diff([o for o, _ in s])
        assert np.isclose(gaps[:, None], quantiles[None, :]).any(1).all()

    def by_rank(s):
        return sorted((sum(1 for _, n in s if n == t) for t in names),
                      reverse=True)

    def top(s):
        return max(names, key=lambda t: sum(1 for _, n in s if n == t))

    assert by_rank(a) == by_rank(b)
    tops = {top(traffic.make_schedule(names, mix, 30, 3_000_000_000 + i))
            for i in range(8)}
    assert len(tops) > 1


def test_closed_loop_order_cycles_every_template():
    order = traffic.make_order(["a", "b", "c"], {"weights": "uniform"}, 9)
    for i in range(0, 30, 3):
        assert sorted(order[i:i + 3]) == ["a", "b", "c"]


# -- correct ------------------------------------------------------------------


@pytest.mark.parametrize("cell", CELLS)
def test_a_sound_run_is_correct(cell, root):
    out = _run(cell, root)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0 and out["failed"] == 0


def _altered(fn):
    def wrapped(*a, **k):
        out = fn(*a, **k)
        if isinstance(out, dict):
            name = next(iter(out))
            out[name] = np.asarray(out[name]) * 1.01
        else:
            name = next(iter(out.aggs))
            vals = np.array(out.aggs[name], np.float64)
            vals[:1] *= 1.01
            out.aggs[name] = vals
        return out
    return wrapped


@pytest.mark.parametrize("cell", CELLS)
def test_an_answer_altered_where_it_is_produced_is_not_correct(cell, root,
                                                               monkeypatch):
    from repro.core import groupby, plan

    monkeypatch.setattr(groupby, "finalize_groupby_partials",
                        _altered(groupby.finalize_groupby_partials))
    monkeypatch.setattr(plan, "finalize_scalar_partials",
                        _altered(plan.finalize_scalar_partials))
    out = _run(cell, root)
    assert not out["correct"]


@pytest.mark.parametrize("cell", CELLS)
def test_half_the_partitions_left_out_is_not_correct(cell, root,
                                                     monkeypatch):
    from repro.core import partition, serve

    real = partition.partition_match_verdict

    def half(part, ops, table):
        ok, cause = real(part, ops, table)
        if ok and (part.row_offset // table.partitions[0].rows) % 2:
            return False, "left out"
        return ok, cause

    monkeypatch.setattr(partition, "partition_match_verdict", half)
    monkeypatch.setattr(serve, "partition_match_verdict", half)
    out = _run(cell, root)
    assert not out["correct"]
