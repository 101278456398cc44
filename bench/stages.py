#!/usr/bin/env python3
"""Device idle time attributed to the engine's stages.

With tracing on, the engine opens a ``repro:<stage>`` profiler annotation
for each stage of a streamed query (``query``, ``prepare``, ``prune``,
``h2d_wait``, ``dispatch``, ``block``, ``fold`` and its child ``d2h``,
``finalize``; ``transfer`` on the transfer thread), so the stages sit in
the same ``.xplane.pb`` as the device's operations, on the profiler's own
clock. ``host_spans`` keeps them from the trace; ``idle_by_stage`` gives
each device-idle interval of the window to the innermost stage open on
the client's thread, the one that carries the ``bench:query:``
annotations. Idle time under no stage, or under the ``query`` root alone,
is ``untraced``; the stages sum to ``window_s - busy_s`` of
``trace.reduce``.

``bench/trace.py`` keeps only the benchmark's own annotations, so
``bench/run.py`` does not call this module yet (PERF.md, Open questions).
Until it does, this file's command line runs one traced cell as
``bench/run.py --trace 1`` does, with the stages kept:

    python3 bench/stages.py --workload <cell> --seed <n> --seconds <s> \\
        [--keep <path.json.gz>]

Its last line is ``run.py``'s result object with ``idle_by_stage`` (in %
of the traced window) added; ``--keep`` writes the extracted trace,
stages included.
"""
from __future__ import annotations

import os
import sys
from typing import Dict, List

PREFIX = "repro:"
ROOT = "query"  # the root span: its self time is untraced
UNTRACED = "untraced"


def host_spans(prof) -> dict:
    """The engine's annotations of a ``jax.profiler.ProfileData``:
    ``spans`` as ``[name, start_ns, dur_ns, thread]`` (``thread`` numbers
    the host lines) and ``query_thread``, the line that carries the
    benchmark's ``bench:query:`` annotations (None if none does)."""
    from trace import QUERY  # bench/trace.py

    spans: List[list] = []
    query_thread = None
    k = 0
    for plane in prof.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    spans.append([ev.name, ev.start_ns, ev.duration_ns, k])
                elif query_thread is None and ev.name.startswith(QUERY):
                    query_thread = k
            k += 1
    return {"spans": spans, "query_thread": query_thread}


def _innermost(spans, w0: float, w1: float):
    """[w0, w1) cut into (start, end, stage) pieces, each labelled with
    the innermost span open over it: the one opened last."""
    events = []
    for i, (_, s, d, _) in enumerate(spans):
        events.append((s, 1, -d, i))  # a longer span opens first
        events.append((s + d, 0, 0, i))  # ends before starts at one time
    events.sort()
    open_: List[int] = []
    pieces = []
    cur = w0

    def label():
        if not open_:
            return UNTRACED
        name = spans[open_[-1]][0][len(PREFIX):]
        return UNTRACED if name == ROOT else name

    for t, opens, _, i in events:
        t = min(max(t, w0), w1)
        if t > cur:
            pieces.append((cur, t, label()))
            cur = t
        if opens:
            open_.append(i)
        else:
            open_.remove(i)
    if cur < w1:
        pieces.append((cur, w1, label()))
    return pieces


def idle_by_stage(ex: dict) -> Dict[str, float]:
    """Seconds of device idle time in the ``bench:window`` under each
    stage, averaged over devices as ``trace.reduce``'s ``busy_s`` is. A
    trace without ``spans`` gives everything to ``untraced``."""
    from trace import WINDOW, _union  # bench/trace.py

    wins = [(s, s + d) for name, s, d in ex["host"] if name == WINDOW]
    if not wins or not ex["devices"]:
        raise ValueError("trace holds no window annotation or no device")
    w0, w1 = wins[0]
    thread = ex.get("query_thread")
    mine = [sp for sp in ex.get("spans", ())
            if thread is not None and sp[3] == thread]
    pieces = _innermost(mine, w0, w1)
    out: Dict[str, float] = {}
    for dev in ex["devices"]:
        busy = _union([(max(s, w0), min(s + d, w1))
                       for _, s, d in dev["ops"]
                       if min(s + d, w1) > max(s, w0)])
        edges = [w0] + [x for se in busy for x in se] + [w1]
        j = 0
        for a, b in zip(edges[0::2], edges[1::2]):
            while j < len(pieces) and pieces[j][1] <= a:
                j += 1
            k = j
            while k < len(pieces) and pieces[k][0] < b:
                s, e, stage = pieces[k]
                over = min(b, e) - max(a, s)
                if over > 0:
                    out[stage] = out.get(stage, 0.0) + over
                k += 1
    ndev = len(ex["devices"])
    return {k: v / ndev / 1e9 for k, v in out.items()}


def idle_share(run, *stages: str):
    """``device.idle_in.*``: idle seconds under ``stages`` over the traced
    window, in %; None where the trace holds no engine stage (a program
    without the annotations, or a reduction without ``idle_by_stage``)."""
    t = run.trace or {}
    by = t.get("idle_by_stage")
    if not by or set(by) == {UNTRACED} or not t.get("window_s"):
        return None
    return 100.0 * sum(by.get(s, 0.0) for s in stages) / t["window_s"]


def main(argv=None, t_start=None) -> int:
    import argparse
    import gzip
    import json

    import harness
    import trace

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--keep", help="write the extracted trace here")
    args = ap.parse_args(argv)
    extract, reduce = trace.extract, trace.reduce
    kept = {}

    def extract_with_stages(path):
        from jax.profiler import ProfileData

        ex = extract(path)
        ex.update(host_spans(ProfileData.from_file(path)))
        kept["ex"] = ex
        return ex

    def reduce_with_stages(ex, outstanding=None):
        out = reduce(ex, outstanding)
        out["idle_by_stage"] = idle_by_stage(ex)
        kept["idle"] = out["idle_by_stage"]
        kept["window_s"] = out["window_s"]
        return out

    trace.extract, trace.reduce = extract_with_stages, reduce_with_stages
    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds, True,
                               t_start=t_start)
    except harness.NoChip as exc:
        print(f"stages: {exc}; nothing was measured", file=sys.stderr)
        return 3
    finally:
        trace.extract, trace.reduce = extract, reduce
    if args.keep:
        with gzip.open(args.keep, "wt") as f:
            json.dump(kept["ex"], f)
    out["idle_by_stage"] = {k: 100.0 * v / kept["window_s"]
                            for k, v in sorted(kept["idle"].items())}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    import time

    # as bench/run.py: the engine traces in string-hash order, so the run
    # starts again with the hash seed fixed and finds the compile cache
    if os.environ.get("PYTHONHASHSEED") != "0":
        os.environ["PYTHONHASHSEED"] = "0"
        os.environ["BENCH_SETUP_T0"] = repr(time.perf_counter())
        os.execv(sys.executable, [sys.executable] + sys.argv)
    t0 = float(os.environ.pop("BENCH_SETUP_T0", time.perf_counter()))
    BENCH = os.path.dirname(os.path.abspath(__file__))
    sys.path[:0] = [os.path.join(os.path.dirname(BENCH), "src"), BENCH]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main(t_start=t0))
