"""Mean host time per flush to threshold labels, build results and publish
them on the bus."""

from benchmark.harness import readers

NAME = "publish_ms"
UNIT = "ms/flush"
LAYER = "transfer and publish"
BETTER = "lower"
SOURCE = "program_span"
MOVES = {"tick_p99_ms": "publish_ms",
         "ticks_per_s": "backlog_publish_ms"}
read = readers.hist_mean_ms("publish")
