"""Share of the train step's device time spent in latent attention's
core: scores over the 192-wide queries and keys, the causal softmax and
the product with the 128-wide values (everything traced under an
``attention_latent`` named scope, forward and backward), over the busy
time of ``jit_train_step``.  Inside ``attention_dev_share``."""

from benchmark.harness import scope_shares

NAME = "mla_core_dev_share"
UNIT = "%"
LAYER = "kernels"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"
read = scope_shares.dev_share("attention_latent")
