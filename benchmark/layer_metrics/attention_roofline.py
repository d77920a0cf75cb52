"""Roofline share of the fused attention cores: the least time the chip
could take for ``q k^T`` and ``p v`` over the visible band of each layout
(``pairs x 28 x 128 x 4`` forward, 2.5 times that backward;
harness/moe_decoder_flops.py) over the device time under the
``attention_full`` and ``attention_window`` scopes in the traced slice.
Projections and rotary are outside these scopes; the recomputation's
repeat of the forward is in the time and not in the operations."""

from benchmark.harness import moe_decoder_flops as flops
from benchmark.harness import scope_shares

NAME = "attention_roofline"
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
    seqs = steps * moe["sequences_per_step"]
    return scope_shares.roofline_share(
        record, ("attention_full", "attention_window"),
        seqs * flops.attention_core_flops_step(
            moe["seq_len"], mc.n_heads, mc.head_dim, mc.sliding_window,
            mc.layer_layout),
        seqs * flops.attention_core_bytes_step(
            moe["seq_len"], mc.n_heads, mc.n_kv_heads, mc.head_dim,
            len(mc.layer_layout)))
