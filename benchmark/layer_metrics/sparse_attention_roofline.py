"""Roofline share of attention over the picked keys: the least time the
chip could take for ``q k^T`` and ``p v`` over the **picked** pairs
(``sum_t min(t + 1, topk) x 32 x 128 x 4`` forward, 2.5 times that
backward; q, k, v, o once each way; harness/sparse_decoder_flops.py)
over the device time under the ``attention_sparse`` scope in the traced
slice.  A kernel that computes every causal pair and masks reads the
lower share it earns; the recomputation's repeat of the forward is in
the time and not in the operations."""

from benchmark.harness import scope_shares
from benchmark.harness import sparse_decoder_flops as flops

NAME = "sparse_attention_roofline"
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
    seqs, n_layers = steps * sparse["sequences_per_step"], len(mc.layer_layout)
    return scope_shares.roofline_share(
        record, ("attention_sparse",),
        seqs * n_layers * flops.sparse_core_flops_step(
            sparse["seq_len"], mc.indexer_topk, mc.n_heads, mc.head_dim),
        seqs * flops.attention_core_bytes_step(
            sparse["seq_len"], mc.n_heads, mc.n_kv_heads, mc.head_dim,
            n_layers))
