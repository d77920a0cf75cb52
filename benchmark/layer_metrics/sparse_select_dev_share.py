"""Share of the train step's device time spent choosing keys: the exact
per-query top-k over the index scores (everything traced under an
``attention_select`` named scope, the recomputation's repeat included),
over the busy time of ``jit_train_step``."""

from benchmark.harness import scope_shares

NAME = "sparse_select_dev_share"
UNIT = "%"
LAYER = "kernels"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"
read = scope_shares.dev_share("attention_select")
