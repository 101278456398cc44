"""The device trace of a ``--trace 1`` run, and its reduction to numbers.

``Profiler`` wraps JAX's profiler around the measured window and marks it,
and each query the client makes, with ``jax.profiler.TraceAnnotation``.
``extract`` keeps what the reduction needs from the ``.xplane.pb`` file;
``reduce`` turns that into device busy and idle time, the time of each
XLA module (one per compiled partition program) and a breakdown: the
device operations that took most time and the longest idle gaps, each
labelled by what the client was doing in it.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import shutil
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench:window"
QUERY = "bench:query:"
TOP = 10


def _short(name: str) -> str:
    """An XLA op's name without its HLO text: ``%fusion.20 = s32[6]...``
    becomes ``fusion.20``."""
    return name.split(" = ", 1)[0].lstrip("%")


def extract(path: str) -> dict:
    """Device op and module events and the benchmark's host annotations
    from one ``.xplane.pb``, as plain lists of ``[name, start_ns, dur_ns]``."""
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(path)
    devices, host = [], []
    planes = {}
    for plane in prof.planes:
        planes[plane.name] = [line.name for line in plane.lines][:12]
        if plane.name.startswith("/device:TPU:") and "SparseCore" not in \
                plane.name:
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key is None:
                    continue
                dev[key].extend([_short(ev.name), ev.start_ns,
                                 ev.duration_ns] for ev in line.events)
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([ev.name, ev.start_ns, ev.duration_ns]
                            for ev in line.events
                            if ev.name.startswith("bench:"))
    return {"devices": devices, "host": host, "planes": planes}


def _union(intervals: Sequence[Tuple[float, float]]):
    """Merge [start, end) intervals; returns the merged, sorted list."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _label(a: float, b: float, host, outstanding) -> str:
    """What the client did in the gap [a, b): the query annotation that
    overlaps it most, or for an open loop how many requests were out."""
    best, most = None, 0.0
    for name, s, d in host:
        if name.startswith(QUERY):
            over = min(b, s + d) - max(a, s)
            if over > most:
                best, most = name[len(QUERY):], over
    if best is not None:
        return "client in " + best
    if outstanding is not None:
        t = (a + b) / 2
        k = sum(1 for s, e in outstanding if s <= t < e)
        return f"{k} requests outstanding" if k else "no request outstanding"
    return "client between queries"


def reduce(ex: dict, outstanding: Optional[List[Tuple[float, float]]] = None
           ) -> dict:
    """Numbers from an ``extract``-ed trace, over the ``bench:window``
    annotation. ``outstanding`` holds open-loop requests as (due, done)
    intervals in nanoseconds on the trace's clock."""
    wins = [(s, s + d) for name, s, d in ex["host"] if name == WINDOW]
    if not wins or not ex["devices"]:
        raise ValueError("trace holds no window annotation or no device")
    w0, w1 = wins[0]
    busy_total = 0.0
    modules: Dict[str, float] = {}
    ops: Dict[str, float] = {}
    gaps = []
    for dev in ex["devices"]:
        spans = []
        for name, s, d in dev["ops"]:
            s0, s1 = max(s, w0), min(s + d, w1)
            if s1 > s0:
                spans.append((s0, s1))
                ops[name] = ops.get(name, 0.0) + (s1 - s0)
        merged = _union(spans)
        busy_total += sum(e - s for s, e in merged)
        for name, s, d in dev["modules"]:
            s0, s1 = max(s, w0), min(s + d, w1)
            if s1 > s0:
                modules[name] = modules.get(name, 0.0) + (s1 - s0)
        edges = [w0] + [x for se in merged for x in se] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, a, b))
    ndev = len(ex["devices"])
    gaps.sort(reverse=True)
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": busy_total / ndev / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "module_s": {k: v / ndev / 1e9 for k, v in modules.items()},
        "breakdown": {
            "device_ops": [[n, v / ndev / 1e9] for n, v in top_ops],
            "idle_gaps": [[_label(a, b, ex["host"], outstanding), g / 1e9]
                          for g, a, b in gaps[:TOP]]},
    }


class Profiler:
    """JAX's profiler over the window, writing under ``root/name``."""

    def __init__(self, root: str, name: str):
        self.dir = os.path.join(root, name)
        self._anchor = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(self.dir)

    def annotate(self, name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)

    def stop(self, outstanding=None) -> dict:
        """Stop, extract, reduce; keeps ``extract``'s output beside the raw
        trace as ``trace.json.gz`` and deletes the raw one.
        ``outstanding``: open-loop (due, done) pairs, in seconds from the
        start of the window."""
        import jax

        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        ex = extract(found[0])
        shutil.rmtree(os.path.join(self.dir, "plugins"), ignore_errors=True)
        with gzip.open(os.path.join(self.dir, "trace.json.gz"), "wt") as f:
            json.dump(ex, f)
        spans = None
        if outstanding is not None:
            # seconds from the window's start, moved onto the trace's clock
            # by the window annotation's own start
            w0 = [s for name, s, d in ex["host"] if name == WINDOW][0]
            spans = [(w0 + a * 1e9, w0 + b * 1e9) for a, b in outstanding]
        return reduce(ex, spans)
