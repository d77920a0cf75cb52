"""Host time of the call into the jitted train step (wrapper, dispatch,
donation wait): mean duration of the ``train`` annotations that started in
the traced slice."""

from benchmark.harness import program_spans

NAME = "train_dispatch_us"
UNIT = "us/step"
LAYER = "train step"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "train_samples_per_s"
read = program_spans.span_mean_us("train")
