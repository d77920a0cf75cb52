"""Share of the train step's device time spent in the gated-delta-rule
layers' mixers, forward, recomputation and backward: the projections,
the three short convolutions, the gates and L2 norms, the chunked walk,
the gated head norm, the output projection and the block's norm of the
mixer's output (everything traced under a ``gdn_mixer`` named scope),
over the busy time of ``jit_train_step``.  A program without the scope
(an older commit, another family) gives nothing."""

from benchmark.harness import scope_shares

NAME = "gdn_mixer_dev_share"
UNIT = "%"
LAYER = "kernels"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"
read = scope_shares.dev_share("gdn_mixer")
