"""Roofline share of the held experts' grouped products: the least time
the chip could take for the products of the pairs the program counted
(``pairs x 6 x hidden x expert width`` forward, twice that backward;
their bytes: harness/moe_decoder_flops.py) over the device time under the
``moe_experts`` scope in the traced slice.  The recomputation's repeat of
the forward is in the time and not in the operations."""

from benchmark.harness import moe_decoder_flops as flops
from benchmark.harness import scope_shares

NAME = "moe_experts_roofline"
UNIT = "%"
LAYER = "kernels"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(record):
    moe, steps = record.get("moe"), scope_shares.traced_train_steps(record)
    if not moe or not steps:
        return None
    mc = record["model_cfg"]
    pairs = sum(moe["pairs_per_train_step"])  # a step, all layers
    return scope_shares.roofline_share(
        record, ("moe_experts",),
        steps * flops.expert_flops_step(
            pairs, mc.hidden_size, mc.moe_ffn_size),
        steps * flops.expert_bytes_step(
            pairs, mc.experts_held[1], mc.hidden_size, mc.moe_ffn_size,
            len(mc.layer_layout)))
