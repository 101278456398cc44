"""Share of the traced window in which no operation ran on the device, in
a served cell (layer: device): the reading of ``device.idle_share.stream``."""
from harness import metric_reader

read = metric_reader("device.idle_share.stream")
