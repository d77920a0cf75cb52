"""Share of the train step's device time spent in the shared expert: the
gated MLP every token passes through beside its routed experts
(everything traced under a ``moe_shared`` named scope, forward,
recomputation and backward), over the busy time of ``jit_train_step``."""

from benchmark.harness import scope_shares

NAME = "moe_shared_dev_share"
UNIT = "%"
LAYER = "kernels"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"
read = scope_shares.dev_share("moe_shared")
