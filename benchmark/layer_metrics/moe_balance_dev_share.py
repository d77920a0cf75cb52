"""Share of the train step's device time spent in the router's
per-sequence balance term: the scores' normalisation and mean over all
the experts, the choice's count and their product (everything traced
under a ``moe_seq_aux`` named scope, forward, recomputation and
backward), over the busy time of ``jit_train_step``.  Beside
``moe_routing_dev_share``; a program without the scope gives nothing."""

from benchmark.harness import scope_shares

NAME = "moe_balance_dev_share"
UNIT = "%"
LAYER = "expert layer"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"
read = scope_shares.dev_share("moe_seq_aux")
