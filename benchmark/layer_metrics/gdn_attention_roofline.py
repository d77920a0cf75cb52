"""Roofline share of the full-attention cores of a model whose other
layers are gated-delta-rule ones: the least time the chip could take for
``q k^T`` and ``p v`` over the causal triangle of each layer of kind 0
(forward, 2.5 times that backward; harness/gdn_decoder_flops.py, which
counts the layers of that kind alone) over the device time under the
``attention_full`` scope in the traced slice.  Projections and the
whole-width q/k norms are outside the scope.
``hybrid_attention_roofline`` reads the same scope in a cell whose record
carries ``hybrid``; this one reads a record that carries ``gdn``."""

from benchmark.harness import gdn_decoder_flops as flops
from benchmark.harness import scope_shares

NAME = "gdn_attention_roofline"
UNIT = "%"
LAYER = "kernels"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(record):
    gdn = record.get("gdn")
    steps = scope_shares.traced_train_steps(record)
    if not gdn or not steps:
        return None
    mc = record["model_cfg"]
    seqs = steps * gdn["sequences_per_step"]
    return scope_shares.roofline_share(
        record, ("attention_full",),
        seqs * flops.attention_cores_flops_step(mc, gdn["seq_len"]),
        seqs * flops.attention_cores_bytes_step(mc, gdn["seq_len"]))
