"""Share of partitions that zone maps skipped, over every query of the
window (layer: zone-map pruning; ``last_stats`` skipped / partitions)."""


def read(run):
    parts = sum(r["stats"].get("partitions", 0) for r in run.records)
    if not parts:
        return None
    return 100.0 * sum(r["stats"].get("skipped", 0)
                       for r in run.records) / parts
