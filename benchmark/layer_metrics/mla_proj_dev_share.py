"""Share of the train step's device time spent in latent attention's
projections: the four latent products, the two latent norms, rotary on
the 64 rotary dims and the output product (everything traced under an
``mla_proj`` named scope, forward, recomputation and backward), over
the busy time of ``jit_train_step``.  Inside ``attention_dev_share``."""

from benchmark.harness import scope_shares

NAME = "mla_proj_dev_share"
UNIT = "%"
LAYER = "kernels"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"
read = scope_shares.dev_share("mla_proj")
