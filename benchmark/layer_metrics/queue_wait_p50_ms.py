"""Median time a tick waited in the micro-batcher before its flush was
assembled: the program's enqueue_to_dispatch histogram over the window
(log-binned: accurate to a bin, 26 %)."""

from benchmark.harness import readers

NAME = "queue_wait_p50_ms"
UNIT = "ms"
LAYER = "admission queue"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "tick_p50_ms"
read = readers.hist_p50_ms("enqueue_to_dispatch")
