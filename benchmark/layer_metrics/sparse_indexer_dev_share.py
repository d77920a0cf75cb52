"""Share of the train step's device time spent in the learned-sparse
layers' indexer: its three projections and the index scores of every
causal pair (everything traced under an ``attention_indexer`` named
scope, the recomputation's repeat included), over the busy time of
``jit_train_step``."""

from benchmark.harness import scope_shares

NAME = "sparse_indexer_dev_share"
UNIT = "%"
LAYER = "kernels"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"
read = scope_shares.dev_share("attention_indexer")
