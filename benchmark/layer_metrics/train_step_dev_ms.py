"""Device time per step: device-plane busy time in the traced slice over
the trainer's step annotations (train and eval) in it."""

from benchmark.harness import readers

NAME = "train_step_dev_ms"
UNIT = "ms/step"
LAYER = "train step"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"
read = readers.train_step_dev_ms
