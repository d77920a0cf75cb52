"""Share of the train step's device time spent making the pairwise
products inside the delta-rule walks' chunks (everything traced under a
``kda_intra`` named scope: ``A`` and ``B`` of ``ops/kda.py``, with the
``(16, 16, 128)`` decays of their diagonal sub-blocks, forward,
recomputation and backward), over the busy time of ``jit_train_step``.
Inside ``kda_scan_dev_share``: where the decays live, in arrays or in a
kernel, is what this share says."""

from benchmark.harness import scope_shares

NAME = "kda_intra_dev_share"
UNIT = "%"
LAYER = "kernels"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"
read = scope_shares.dev_share("kda_intra")
