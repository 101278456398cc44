"""Queries per scan pass over the window (layer: serve; shared and solo
queries over passes, from ``QueryServer.stats()``)."""


def read(run):
    passes = run.server.get("passes", 0)
    return run.server.get("queries", 0) / passes if passes else None
