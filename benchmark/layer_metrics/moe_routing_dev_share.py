"""Share of the train step's device time spent getting tokens to the held
experts and back: the router (``moe_route``: product, softmax, top-6),
the sort and gather of pairs (``moe_dispatch``) and the gated gather back
(``moe_combine``), over the busy time of ``jit_train_step``."""

from benchmark.harness import scope_shares

NAME = "moe_routing_dev_share"
UNIT = "%"
LAYER = "expert layer"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"
read = scope_shares.dev_share("moe_route", "moe_dispatch", "moe_combine")
