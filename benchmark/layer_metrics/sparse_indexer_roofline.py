"""Roofline share of the index scores: the least time the chip could
take for ``qI . kI`` over the causal pairs (``pairs x 16 x 64 x 2`` a
layer, forward only: the indexer has no backward) and their least bytes
(harness/sparse_decoder_flops.py) over the device time under the
``attention_indexer`` scope in the traced slice.  The indexer's three
projections and the recomputation's repeat are in the time and not in
the operations."""

from benchmark.harness import scope_shares
from benchmark.harness import sparse_decoder_flops as flops

NAME = "sparse_indexer_roofline"
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
    mc = record["model_cfg"]
    layers = steps * sparse["sequences_per_step"] * len(mc.layer_layout)
    return scope_shares.roofline_share(
        record, ("attention_indexer",),
        layers * flops.indexer_score_flops(
            sparse["seq_len"], mc.indexer_heads, mc.indexer_head_dim),
        layers * flops.indexer_score_bytes(
            sparse["seq_len"], mc.indexer_heads, mc.indexer_head_dim))
