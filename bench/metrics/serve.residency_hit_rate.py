"""Share of partition fetches the residency LRU answered without a
transfer, over the window (layer: serve; ``QueryServer.stats()``)."""


def read(run):
    hits = run.server.get("residency_hits", 0)
    total = hits + run.server.get("residency_misses", 0)
    return 100.0 * hits / total if total else None
