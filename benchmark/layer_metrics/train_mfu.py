"""Model FLOP/s utilization: analytic forward+backward FLOPs per valid
window (benchmark/harness/flops.py) times train_samples_per_s over the
chips' peak bf16 FLOP/s.  End to end, validation passes and epoch
boundaries included; not a kernel's roofline share."""

from benchmark.harness import readers

NAME = "train_mfu"
UNIT = "%"
LAYER = "train step"
BETTER = "higher"
SOURCE = "host_clock"
MOVES = "train_samples_per_s"
read = readers.train_mfu
