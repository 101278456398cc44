"""Share of the window the host spent preparing join sides (layer:
join-side preparation, host): the sum of each query's
``last_stats["join_prep_ms"]``, its ``join_side`` spans (dimension
decode, filter, key translation, map or sorted-key build), over the
window. None for a program that does not report it."""


def read(run):
    ms = [r["stats"]["join_prep_ms"] for r in run.records
          if "join_prep_ms" in r["stats"]]
    if not ms or not run.window_s:
        return None
    return 100.0 * sum(ms) / 1e3 / run.window_s
