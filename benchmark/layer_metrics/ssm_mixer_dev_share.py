"""Share of the train step's device time spent in the state-space
layers' mixers, forward, recomputation and backward: the input
projection, the convolution, the scan, the gated norm and the output
projection (everything traced under an ``ssm_mixer`` named scope), over
the busy time of ``jit_train_step``."""

from benchmark.harness import scope_shares

NAME = "ssm_mixer_dev_share"
UNIT = "%"
LAYER = "kernels"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"
read = scope_shares.dev_share("ssm_mixer")
