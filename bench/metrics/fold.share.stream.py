"""Share of the window spent folding partials on the host (layer: host
fold; the sum of ``StreamStats.merge_ms``, the ``fold`` spans)."""


def read(run):
    if not run.records or not run.window_s:
        return None
    ms = sum(r["stats"].get("merge_ms", 0.0) for r in run.records)
    return 100.0 * ms / 1e3 / run.window_s
