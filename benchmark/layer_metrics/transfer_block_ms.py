"""Mean time FleetGateway._complete was blocked fetching a flush's
probabilities to the host (the program's histogram calls it 'device'; it
is the unhidden wait, not device time)."""

from benchmark.harness import readers

NAME = "transfer_block_ms"
UNIT = "ms/flush"
LAYER = "transfer and publish"
BETTER = "lower"
SOURCE = "program_span"
MOVES = {"tick_p50_ms": "transfer_block_ms",
         "ticks_per_s": "backlog_transfer_block_ms"}
read = readers.hist_mean_ms("device")
