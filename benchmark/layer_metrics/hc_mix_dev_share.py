"""Share of the train step's device time spent in the hyper-connections:
the coefficient product, the sigmoids and Sinkhorn's turns
(``hc_coeff``), the lanes' read (``hc_pre``), their remix and the
sublayer's write (``hc_post_res``) and the exit's sum (everything traced
under a ``hyper_conn`` named scope, forward, recomputation and
backward), over the busy time of ``jit_train_step``."""

from benchmark.harness import scope_shares

NAME = "hc_mix_dev_share"
UNIT = "%"
LAYER = "kernels"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"
read = scope_shares.dev_share("hyper_conn")
