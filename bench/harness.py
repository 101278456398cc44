"""One run of one benchmark cell: set-up, the measured window, the check.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own and is found by the name that
``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``: the deployment's sizes, source and
  guarantees, and the limits of the comparison that decides ``correct``;
* ``bench/configs/<config>.py``: its seeded generator, its query templates
  and its float64 numpy reference;
* ``bench/mixes/<traffic>.json``: the traffic's parameters, read by the
  one generator in ``bench/traffic.py``;
* ``bench/metrics/<metric>.py``: a ``read(run)`` that takes one per-layer
  metric from the run's counters, spans and device trace.

``run_cell`` is the whole run; ``bench/run.py`` is its command line.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import sys
import time
from types import SimpleNamespace
from typing import Dict, List, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(BENCH, ".jax_cache")
TRACE_DIR = os.path.join(BENCH, ".traces")
RESULT_WAIT_S = 120.0  # how long past the window a request may still answer


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell needs."""


# ---------------------------------------------------------------------------
# discovery by name
# ---------------------------------------------------------------------------


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"BENCHMARK.json has no {what} named {name!r}")


def load_module(path: str):
    """Import a file of the benchmark by its path (its name may hold
    characters a Python module name may not)."""
    name = "bench_" + os.path.relpath(path, BENCH).replace(os.sep, "_") \
        .replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """A workload entry with its configuration, mix and metrics resolved."""

    name: str
    chips: int
    config: dict  # bench/configs/<config>.json
    module: object  # bench/configs/<config>.py
    mix: dict  # bench/mixes/<traffic>.json
    end_to_end: List[dict]
    per_layer: List[dict]


def load_config(file: str, root: str = ROOT):
    """A configuration's sizes (its JSON ``file``) and its module (the
    ``.py`` beside it)."""
    with open(os.path.join(root, file)) as f:
        config = json.load(f)
    return config, load_module(os.path.join(root, os.path.splitext(file)[0]
                                            + ".py"))


def load_mix(traffic: str, root: str = ROOT) -> dict:
    with open(os.path.join(root, "bench", "mixes", traffic + ".json")) as f:
        return json.load(f)


def resolve(bench: dict, workload: str, root: str = ROOT) -> Cell:
    w = _by_name(bench["workloads"], workload, "workload")
    conf = _by_name(bench["configs"], w["config"], "config")
    config, module = load_config(conf["file"], root)
    mix = load_mix(w["traffic"], root)

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    return Cell(name=workload, chips=int(w["chips"]), config=config,
                module=module, mix=mix,
                end_to_end=[m for m in bench["end_to_end"] if applies(m)],
                per_layer=[m for m in bench["per_layer"] if applies(m)])


def metric_reader(name: str, root: str = ROOT):
    """The ``read(run)`` function of per-layer metric ``name``."""
    return load_module(os.path.join(root, "bench", "metrics",
                                    name + ".py")).read


# ---------------------------------------------------------------------------
# the device and the compile cache
# ---------------------------------------------------------------------------


def require_chips(n: int):
    import jax

    devs = jax.devices()
    if devs[0].platform == "cpu" or len(devs) < n:
        raise NoChip(f"JAX finds {len(devs)} {devs[0].platform} device(s); "
                     f"this cell needs {n} accelerator chip(s)")
    return devs


def place_compile_cache() -> None:
    """JAX's persistent compile cache at a fixed path inside the checkout,
    so that only a checkout's first run of a cell compiles."""
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class CompileCounter:
    """Counts the programs JAX builds for a backend (``programs``), how
    many of them the persistent compile cache answered (``cache_hits``),
    and the seconds spent tracing, lowering and compiling or loading."""

    STAGES = {"/jax/core/compile/jaxpr_trace_duration": "trace",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
              "/jax/core/compile/backend_compile_duration": "compile"}

    def __init__(self):
        import jax

        self.programs = 0
        self.cache_hits = 0
        self.seconds = {v: 0.0 for v in self.STAGES.values()}
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event, duration, **kw):
        stage = self.STAGES.get(event)
        if stage is not None:
            self.seconds[stage] += duration
            self.programs += stage == "compile"

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def __str__(self):
        secs = ", ".join(f"{k} {v:.3f} s" for k, v in self.seconds.items())
        return (f"{self.programs} programs built, {self.cache_hits} of them "
                f"from the compile cache ({secs})")


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------


def engine_api():
    """What a template may call, handed to the config module."""
    from repro.core.arithmetic import binary_op
    from repro.core.plan import col

    return SimpleNamespace(col=col, binary_op=binary_op)


def ingest(data: dict, config: dict):
    """The fact table as a PartitionedTable, dimensions as resident Tables."""
    from repro.core import compress
    from repro.core.partition import PartitionedTable
    from repro.core.table import Table

    table = PartitionedTable.from_arrays(
        data["fact"], cfg=compress.CompressionConfig(),
        partition_rows=int(config["partition_rows"]),
        pack=bool(config["pack"]))
    dims = {name: Table.from_arrays(cols)
            for name, cols in data["dims"].items()}
    return table, dims


def answer_of(result, table, dims, keys: List[str]) -> dict:
    """The engine's result in the reference's form: group keys decoded to
    their values, aggregates as floats, rows in the order returned."""
    if isinstance(result, dict):
        return {"keys": [], "rows": [((), {k: float(np.asarray(v))
                                            for k, v in result.items()})]}
    n = int(result.num_groups)
    cols = []
    for k in keys:
        codes = np.asarray(result.keys[k])[:n]
        dic = table.dictionaries.get(k)
        if dic is None:
            for d in dims.values():
                if k in d.columns:
                    dic = d.dictionaries.get(k)
                    break
        cols.append(dic[codes] if dic is not None else codes)
    aggs = {a: np.asarray(v, np.float64)[:n] for a, v in result.aggs.items()}
    return {"keys": list(keys),
            "rows": [(tuple(c[i].item() for c in cols),
                      {a: float(v[i]) for a, v in aggs.items()})
                     for i in range(n)]}


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Run:
    """What the window leaves for the metrics and the check."""

    cell: Cell
    table: object = None
    dims: Dict[str, object] = None
    window_s: float = 0.0
    records: List[dict] = dataclasses.field(default_factory=list)
    attempted: int = 0
    failed: int = 0
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    server: Dict[str, float] = dataclasses.field(default_factory=dict)
    trace: Optional[dict] = None
    device_kind: str = ""
    notes: List[str] = dataclasses.field(default_factory=list)

    @property
    def rows_covered(self) -> int:
        return sum(r["rows"] for r in self.records)

    def template_columns(self, name: str):
        return self.cell.module.COLUMNS[name]


def _counter_delta(before: dict, after: dict) -> dict:
    keys = set(before) | set(after)
    return {k: after.get(k, 0) - before.get(k, 0) for k in keys}


def _server_counts(server) -> dict:
    s = server.stats()
    return {"residency_hits": s["residency"]["hits"],
            "residency_misses": s["residency"]["misses"],
            "passes": s["scans"]["passes"],
            "queries": s["scans"]["shared_queries"] + s["scans"]["solo_queries"],
            "errors": s["errors"]}


def stream_window(run: Run, queries: dict, order: List[str], seconds: float,
                   annotate) -> float:
    """Closed loop, one client: each staged template's ``run()`` in turn,
    in whole cycles of the templates, until ``seconds`` have passed; so
    every seed's window does the same work. The window ends with the last
    answer of the last cycle."""
    table = run.table
    cycle = len(queries)
    t0 = time.perf_counter()
    deadline = t0 + seconds
    i = 0
    t = t0
    while t < deadline or i % cycle:
        name = order[i]
        i += 1
        q = queries[name]
        run.attempted += 1
        with annotate(f"bench:query:{name}"):
            try:
                result = q.run()
            except Exception as exc:  # noqa: BLE001 - a failed query counts
                run.failed += 1
                run.notes.append(f"{name}: {type(exc).__name__}: {exc}")
                t = time.perf_counter()
                continue
        t1 = time.perf_counter()
        run.records.append({
            "template": name, "latency_ms": (t1 - t) * 1e3,
            "rows": table.nrows, "result": result,
            "stats": dict(q.last_stats),
            "executed_parts": [v[0] for v in q.last_verdicts if v[1]]})
        t = t1
    return t - t0


def serve_window(run: Run, server, stage, schedule, annotate) -> float:
    """Open loop: submit each request at its due time; latency runs from
    the due time to the answer. Returns the window's length."""
    table = run.table
    tickets = []
    t0 = time.perf_counter()
    late = []
    for offset, name in schedule:
        due = t0 + offset
        now = time.perf_counter()
        if due > now:
            time.sleep(due - now)
        sent = time.perf_counter()
        late.append(sent - due)
        run.attempted += 1
        try:
            ticket = server.submit(stage(name))
        except Exception as exc:  # noqa: BLE001 - a refused request counts
            run.failed += 1
            run.notes.append(f"{name}: submit {type(exc).__name__}: {exc}")
            continue
        tickets.append((name, due, ticket))
    window = max(schedule[-1][0] if schedule else 0.0,
                 time.perf_counter() - t0)
    late_ms = np.asarray(late) * 1e3
    run.notes.append(
        f"generator lateness: median {np.median(late_ms):.3f} ms, p95 "
        f"{np.percentile(late_ms, 95):.3f} ms, max {late_ms.max():.3f} ms "
        f"over {len(late_ms)} requests")
    for name, due, ticket in tickets:
        try:
            result = server.result(ticket, timeout=RESULT_WAIT_S)
        except Exception as exc:  # noqa: BLE001 - a failed request counts
            run.failed += 1
            run.records.append({"template": name, "latency_ms": float("inf"),
                                "rows": table.nrows, "result": None,
                                "stats": {}, "due": due})
            run.notes.append(f"{name}: {type(exc).__name__}: {exc}")
            continue
        done = ticket.submitted + ticket.latency_ms / 1e3
        run.records.append({
            "template": name, "latency_ms": (done - due) * 1e3,
            "rows": table.nrows, "result": result,
            "stats": dict(ticket.stats or {}), "due": due, "done": done})
    return window


def _percentile(values, q) -> float:
    v = np.asarray(values, np.float64)
    if not len(v):
        return float("nan")
    if np.isinf(v).any():
        # a failed request misses every limit: rank it past the others
        finite_max = v[np.isfinite(v)].max() if np.isfinite(v).any() else 0.0
        v = np.where(np.isfinite(v), v, max(finite_max, 0.0) * 10 + 1e9)
    return float(np.percentile(v, q))


def end_to_end(run: Run, setup_s: float) -> dict:
    lat = [r["latency_ms"] for r in run.records]
    out = {"setup_s": setup_s}
    if run.cell.mix["executor"] == "stream":
        out["scan_rows_per_s"] = run.rows_covered / run.window_s
        out["stream_p95_ms"] = _percentile(lat, 95)
    else:
        out["served_p50_ms"] = _percentile(lat, 50)
        out["served_p95_ms"] = _percentile(lat, 95)
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, overrides: Optional[dict] = None,
             root: str = ROOT, t_start: Optional[float] = None,
             log=None) -> dict:
    """One whole run of a cell; returns the result object that
    ``bench/run.py`` prints as its last line. ``overrides`` replaces keys
    of the configuration (tests run a cell at a small size with it)."""
    t_start = time.perf_counter() if t_start is None else t_start
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    from traffic import make_order, make_schedule  # bench/traffic.py
    import check as check_mod  # bench/check.py

    bench = load_benchmark(root)
    cell = resolve(bench, workload, root)
    config = dict(cell.config, **(overrides or {}))
    import jax

    devs = require_chips(cell.chips) if require_chip else jax.devices()
    dev = devs[0]
    if require_chip:
        place_compile_cache()
    compiles = CompileCounter()
    from repro.core.partition import PartitionedQuery
    from repro.core.serve import QueryServer
    from repro.kernels import dispatch

    if trace:
        dispatch.set_policy(dataclasses.replace(dispatch.policy(),
                                                enable_trace=True))
    mod = cell.module
    mix = cell.mix
    names = list(config["templates"])
    run = Run(cell=cell, device_kind=dev.device_kind)

    t = time.perf_counter()
    data = mod.generate(seed, config)
    t_gen = time.perf_counter() - t
    t = time.perf_counter()
    run.table, run.dims = ingest(data, config)
    t_ingest = time.perf_counter() - t
    log(f"data: {run.table.nrows} rows in {len(run.table.partitions)} "
        f"partitions, {run.table.nbytes()} bytes stored; generated in "
        f"{t_gen:.3f} s, ingested in {t_ingest:.3f} s")
    stage_fns = mod.templates(engine_api())

    def stage(name, q=None):
        return stage_fns[name](q if q is not None
                               else PartitionedQuery(run.table), run.dims)

    window_s = float(seconds)
    server = None
    t = time.perf_counter()
    warm = []
    if mix["executor"] == "stream":
        queries = {n: stage(n) for n in names}
        for n in names:  # compile, or load from the cache, every program
            t1 = time.perf_counter()
            queries[n].run()
            warm.append(f"{n} {time.perf_counter() - t1:.3f}")
        order = make_order(names, mix, seed)
    elif mix["executor"] == "serve":
        server = QueryServer(run.table, budget_bytes=run.table.nbytes())
        for n in names:  # fill the residency LRU and the plan cache
            t1 = time.perf_counter()
            server.result(server.submit(stage(n)), timeout=RESULT_WAIT_S)
            warm.append(f"{n} {time.perf_counter() - t1:.3f}")
        schedule = make_schedule(names, mix, window_s, seed)
    else:
        raise ValueError(f"unknown executor {mix['executor']!r}")
    t_warm = time.perf_counter() - t
    log(f"warm-up: {len(names)} templates in {t_warm:.3f} s "
        f"({', '.join(warm)} s); {compiles}")

    from repro.core import telemetry

    counters0 = telemetry.registry().counters()
    server0 = _server_counts(server) if server else {}
    compiles0 = compiles.programs
    profiler = None
    if trace:
        import trace as trace_mod  # bench/trace.py

        profiler = trace_mod.Profiler(TRACE_DIR, f"{workload}-{seed}")
        profiler.start()
    annotate = (profiler.annotate if profiler
                else (lambda name: NO_SPAN))
    setup_s = time.perf_counter() - t_start
    w_t0 = time.perf_counter()
    with annotate("bench:window"):
        if server is None:
            run.window_s = stream_window(run, queries, order, window_s,
                                         annotate)
        else:
            run.window_s = serve_window(run, server, stage, schedule,
                                        annotate)
    if profiler:
        run.trace = profiler.stop(
            outstanding=[(r["due"] - w_t0, r["done"] - w_t0)
                         for r in run.records if "done" in r]
            if server is not None else None)
    run.counters = _counter_delta(counters0, telemetry.registry().counters())
    if server is not None:
        s1 = _server_counts(server)
        run.server = {k: s1[k] - server0[k] for k in s1}
    in_window = compiles.programs - compiles0
    for note in run.notes:
        log(note)
    log(f"window: {run.window_s:.3f} s, {run.attempted} attempted, "
        f"{run.failed} failed, {in_window} programs built inside it")
    mem = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs),
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}

    if trace:
        metrics = {}
        for m in cell.per_layer:
            value = metric_reader(m["name"], root)(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
    else:
        e2e = end_to_end(run, setup_s)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    # the program's state is freed before the reference runs
    answers = [(r["template"], None if r["result"] is None else
                answer_of(r["result"], run.table, run.dims,
                          _keys_of(r["result"])))
               for r in run.records]
    if server is not None:
        server.close()
    del server
    run.table = run.dims = None
    for r in run.records:
        r["result"] = None
    gc.collect()
    t = time.perf_counter()
    checks = check_mod.compare(mod, data, answers, config["check"],
                               missing=run.failed)
    log(f"reference: {len(set(n for n, _ in answers))} templates, "
        f"{len(answers)} answers compared in "
        f"{time.perf_counter() - t:.3f} s")
    correct = check_mod.verdict(checks)
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    out = {"correct": bool(correct), "attempted": run.attempted,
           "failed": run.failed, "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = run.trace["breakdown"]
    out["checks"] = checks
    return out


def _keys_of(result) -> List[str]:
    return [] if isinstance(result, dict) else list(result.keys)


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NO_SPAN = _Null()  # ``annotate`` of an untraced run
