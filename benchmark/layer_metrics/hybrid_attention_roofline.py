"""Roofline share of the hybrid decoder's attention cores: the least
time the chip could take for ``q k^T`` and ``p v`` over the causal
triangle of each attention layer (``pairs x heads x head_dim x 4``
forward, 2.5 times that backward; harness/hybrid_decoder_flops.py) over
the device time under the ``attention_full`` scope in the traced slice.
Projections are outside the scope.  ``attention_roofline`` reads the
same scope in a cell whose record carries ``moe``; this one reads a
record that carries ``hybrid``."""

from benchmark.harness import hybrid_decoder_flops as flops
from benchmark.harness import scope_shares

NAME = "hybrid_attention_roofline"
UNIT = "%"
LAYER = "kernels"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(record):
    hybrid = record.get("hybrid")
    steps = scope_shares.traced_train_steps(record)
    if not hybrid or not steps:
        return None
    mc = record["model_cfg"]
    seqs = steps * hybrid["sequences_per_step"]
    return scope_shares.roofline_share(
        record, ("attention_full",),
        seqs * flops.attention_cores_flops_step(mc, hybrid["seq_len"]),
        seqs * flops.attention_cores_bytes_step(mc, hybrid["seq_len"]))
