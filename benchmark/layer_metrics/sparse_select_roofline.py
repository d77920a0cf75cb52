"""Roofline share of the selection: the least time the chip could take
to read the index scores once and write the picks once (5 B a causal
pair; there are no products, the bound is memory's;
harness/sparse_decoder_flops.py) over the device time under the
``attention_select`` scope in the traced slice.  The recomputation's
repeat is in the time and not in the bytes."""

from benchmark.harness import scope_shares
from benchmark.harness import sparse_decoder_flops as flops

NAME = "sparse_select_roofline"
UNIT = "%"
LAYER = "kernels"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(record):
    sparse = record.get("sparse")
    steps = scope_shares.traced_train_steps(record)
    if not sparse or not steps:
        return None
    layers = (steps * sparse["sequences_per_step"]
              * len(record["model_cfg"].layer_layout))
    return scope_shares.roofline_share(
        record, ("attention_select",), 0.0,
        layers * flops.select_bytes(sparse["seq_len"]))
