"""Device time per flush: device-plane busy time in the traced slice over
the pool_flush annotations in it."""

from benchmark.harness import readers

NAME = "pool_step_dev_us"
UNIT = "us/flush"
LAYER = "pool step"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = {"tick_p99_ms": "pool_step_dev_us",
         "ticks_per_s": "backlog_pool_step_dev_us"}
read = readers.pool_step_dev_us
