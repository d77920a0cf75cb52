"""Share of the train step's device time spent in the chunked scans over
matrix-valued state (everything traced under an ``ssd_scan`` named
scope: the products inside a chunk, the chunks' end states, the carry
over the chunks and the carried state's part of the output, forward,
recomputation and backward), over the busy time of ``jit_train_step``.
Inside ``ssm_mixer_dev_share``."""

from benchmark.harness import scope_shares

NAME = "ssd_scan_dev_share"
UNIT = "%"
LAYER = "kernels"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"
read = scope_shares.dev_share("ssd_scan")
