"""H2D bytes per row answered (layer: H2D transfer): the always-on
``h2d_bytes`` counter's growth over the window, over the rows the window's
queries covered, pruned rows included."""


def read(run):
    rows = run.rows_covered
    if not rows:
        return None
    return run.counters.get("h2d_bytes", 0) / rows
