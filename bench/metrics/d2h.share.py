"""Share of the window the client's thread spent fetching partials to the
host (layer: D2H of partials; the sum of ``StreamStats.d2h_ms``, the
``d2h`` spans). None for a program that does not report ``d2h_ms``."""


def read(run):
    if not run.records or not run.window_s:
        return None
    ms = [r["stats"]["d2h_ms"] for r in run.records if "d2h_ms" in r["stats"]]
    if not ms:
        return None
    return 100.0 * sum(ms) / 1e3 / run.window_s
