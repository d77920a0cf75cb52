"""Share of the train step's device time spent in the held experts' grouped
products and the ReGLU between them: busy time of operations traced under
a ``moe_experts`` named scope (forward, recomputation and backward) over
the busy time of the ``jit_train_step`` program, in the traced slice."""

from benchmark.harness import scope_shares

NAME = "moe_experts_dev_share"
UNIT = "%"
LAYER = "kernels"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"
read = scope_shares.dev_share("moe_experts")
