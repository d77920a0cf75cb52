"""Mean host time of FleetGateway._dispatch per flush: stale filter,
staging assembly, host-to-device copy and the async enqueue of the pool
step."""

from benchmark.harness import readers

NAME = "dispatch_ms"
UNIT = "ms/flush"
LAYER = "flush dispatch"
BETTER = "lower"
SOURCE = "program_span"
MOVES = {"tick_p99_ms": "dispatch_ms",
         "ticks_per_s": "backlog_dispatch_ms"}
read = readers.hist_mean_ms("dispatch")
