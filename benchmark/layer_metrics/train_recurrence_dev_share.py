"""Share of the train step's device time spent in the recurrence: busy
time of operations traced under a ``recurrence_*`` named scope over the
busy time of the ``jit_train_step`` program, in the traced slice."""

from benchmark.harness import program_spans

NAME = "train_recurrence_dev_share"
UNIT = "%"
LAYER = "kernels"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"
read = program_spans.recurrence_dev_share
