"""Host time of folding a step's loss and metrics into the running
accumulators: mean duration of the ``train_fold`` spans in the traced
slice."""

from benchmark.harness import program_spans

NAME = "train_fold_us"
UNIT = "us/step"
LAYER = "train step"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "train_samples_per_s"
read = program_spans.span_mean_us("train_fold")
