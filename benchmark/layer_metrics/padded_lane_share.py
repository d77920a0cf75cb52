"""Share of dispatched lanes that were padding: padded_lanes /
(padded_lanes + ticks_served)."""

from benchmark.harness import readers

NAME = "padded_lane_share"
UNIT = "%"
LAYER = "admission queue"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = {"tick_p99_ms": "padded_lane_share",
         "ticks_per_s": "backlog_padded_lane_share"}
read = readers.padded_lane_share
