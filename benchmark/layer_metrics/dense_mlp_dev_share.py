"""Share of the train step's device time spent in the layers' dense
gated MLPs, forward, recomputation and backward (everything traced under
a ``dense_mlp`` named scope), over the busy time of ``jit_train_step``."""

from benchmark.harness import scope_shares

NAME = "dense_mlp_dev_share"
UNIT = "%"
LAYER = "kernels"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"
read = scope_shares.dev_share("dense_mlp")
