"""Share of the window the step loop spent waiting on input: the sum of the
program's train_input_stall_seconds over the window."""

from benchmark.harness import readers

NAME = "input_stall_share"
UNIT = "%"
LAYER = "input pipeline"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "train_samples_per_s"
read = readers.input_stall_share
