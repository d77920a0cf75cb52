"""Share of the train step's device time spent in the head and the loss:
the chunked product with the untied head (``lm_head``) and the softmax
cross-entropy around it (``loss``), recomputation and backward included,
over the busy time of ``jit_train_step``."""

from benchmark.harness import scope_shares

NAME = "lm_head_dev_share"
UNIT = "%"
LAYER = "train step"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"
_share = scope_shares.dev_share("lm_head", "loss")


def read(record):
    # a classifier's step has a `loss` scope and no head product: only
    # a step that writes `lm_head` has this share
    if scope_shares.scope_seconds(record, ("lm_head",)) is None:
        return None
    return _share(record)
