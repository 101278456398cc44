"""Share of the HBM roofline the partition programs reach (layer: device
program + kernels): the stored bytes of the columns each query references,
in the partitions it executed, over the device's HBM bandwidth, over the
device time of the partition programs in the trace."""
from roofline import peaks, program_seconds, referenced_bytes


def read(run):
    if not run.trace:
        return None
    secs = program_seconds(run.trace["module_s"])
    if secs <= 0:
        return None
    nbytes = sum(referenced_bytes(run.table,
                                  run.template_columns(r["template"]),
                                  r["executed_parts"])
                 for r in run.records if "executed_parts" in r)
    if not nbytes:
        return None
    bw = peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * nbytes / bw / secs
