"""The input pipeline as the step loop meets it: mean duration of the
``train_next_batch`` spans (the loop's ``next()`` on the cached list or the
prefetch queue) in the traced slice."""

from benchmark.harness import program_spans

NAME = "train_next_batch_us"
UNIT = "us/step"
LAYER = "input pipeline"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "train_samples_per_s"
read = program_spans.span_mean_us("train_next_batch")
