"""Share of the window the server's drain thread spent folding partials
(layer: host fold; each ticket's ``merge_ms``, the ``serve.fold`` spans):
the reading of ``fold.share.stream`` over the served requests."""
from harness import metric_reader

read = metric_reader("fold.share.stream")
