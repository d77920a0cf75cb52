"""Ticks served per flush in the window (ticks_served / flushes)."""

from benchmark.harness import readers

NAME = "flush_fill"
UNIT = "ticks/flush"
LAYER = "admission queue"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = {"tick_p99_ms": "flush_fill",
         "ticks_per_s": "backlog_flush_fill"}
read = readers.flush_fill
