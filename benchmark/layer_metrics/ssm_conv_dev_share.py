"""Share of the train step's device time spent in the state-space
layers' causal depthwise convolution and the activation after it
(everything traced under an ``ssm_conv`` named scope), over the busy
time of ``jit_train_step``.  Inside ``ssm_mixer_dev_share``."""

from benchmark.harness import scope_shares

NAME = "ssm_conv_dev_share"
UNIT = "%"
LAYER = "kernels"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"
read = scope_shares.dev_share("ssm_conv")
