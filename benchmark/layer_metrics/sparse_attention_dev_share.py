"""Share of the train step's device time spent in attention over the
picked keys, forward and backward (everything traced under an
``attention_sparse`` named scope), over the busy time of
``jit_train_step``."""

from benchmark.harness import scope_shares

NAME = "sparse_attention_dev_share"
UNIT = "%"
LAYER = "kernels"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"
read = scope_shares.dev_share("attention_sparse")
