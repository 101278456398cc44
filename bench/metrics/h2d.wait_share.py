"""Share of the window the client's thread waited on transfers (layer:
H2D transfer; the sum of ``StreamStats.h2d_ms``, the ``h2d_wait`` spans)."""


def read(run):
    if not run.records or not run.window_s:
        return None
    ms = sum(r["stats"].get("h2d_ms", 0.0) for r in run.records)
    return 100.0 * ms / 1e3 / run.window_s
