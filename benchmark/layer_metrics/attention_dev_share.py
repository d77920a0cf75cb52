"""Share of the train step's device time spent in attention: projections,
rotary and the fused cores of full and window layers alike (everything
traced under an ``attention`` named scope), over the busy time of
``jit_train_step``."""

from benchmark.harness import scope_shares

NAME = "attention_dev_share"
UNIT = "%"
LAYER = "kernels"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"
read = scope_shares.dev_share("attention")
