"""Share of the train step's device time spent in the delta-rule layers'
mixers, forward, recomputation and backward: the projections, the three
short convolutions, the gates and L2 norms, the chunked walk, the gated
head norm and the output projection (everything traced under a
``kda_mixer`` named scope), over the busy time of ``jit_train_step``.  A
program without the scope (an older commit, another family) gives
nothing."""

from benchmark.harness import scope_shares

NAME = "kda_mixer_dev_share"
UNIT = "%"
LAYER = "kernels"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"
read = scope_shares.dev_share("kda_mixer")
