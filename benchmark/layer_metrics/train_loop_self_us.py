"""The step loop's own Python: step-thread time in the traced slice under
no program span, over the ``train`` steps started in it."""

from benchmark.harness import program_spans

NAME = "train_loop_self_us"
UNIT = "us/step"
LAYER = "train step"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "train_samples_per_s"
read = program_spans.loop_self_us
