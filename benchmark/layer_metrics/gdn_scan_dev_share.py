"""Share of the train step's device time spent in the chunked walks of
the delta rule with one decay a head (everything traced under a
``gdn_scan`` named scope: the pairwise products inside a chunk, the
solve, the carry over the chunks and the outputs, forward, recomputation
and backward), over the busy time of ``jit_train_step``.  Inside
``gdn_mixer_dev_share``."""

from benchmark.harness import scope_shares

NAME = "gdn_scan_dev_share"
UNIT = "%"
LAYER = "kernels"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"
read = scope_shares.dev_share("gdn_scan")
