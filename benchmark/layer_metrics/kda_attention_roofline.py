"""Roofline share of the latent-attention cores of a model whose other
layers are delta-rule ones: the least time the chip could take for ``q
k^T`` over the score width and ``p v`` over the value width across the
causal triangle of each layer of kind 4 (forward, 2.5 times that
backward; harness/kda_decoder_flops.py, which counts the layers of that
kind alone) over the device time under the ``attention_latent`` scope in
the traced slice.  Projections are outside the scope.
``mla_core_roofline`` reads the same scope in a cell whose record
carries ``latent`` and counts a core for every layer; this one reads a
record that carries ``kda``."""

from benchmark.harness import kda_decoder_flops as flops
from benchmark.harness import scope_shares

NAME = "kda_attention_roofline"
UNIT = "%"
LAYER = "kernels"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(record):
    kda = record.get("kda")
    steps = scope_shares.traced_train_steps(record)
    if not kda or not steps:
        return None
    mc = record["model_cfg"]
    seqs = steps * kda["sequences_per_step"]
    return scope_shares.roofline_share(
        record, ("attention_latent",),
        seqs * flops.attention_cores_flops_step(mc, kda["seq_len"]),
        seqs * flops.attention_cores_bytes_step(mc, kda["seq_len"]))
