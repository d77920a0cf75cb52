"""Roofline share of latent attention's cores: the least time the chip
could take for ``q k^T`` over the score width (128 + 64) and ``p v`` over
the value width (128) over the causal triangle of every layer (forward,
2.5 times that backward; harness/latent_decoder_flops.py: nothing padded
is counted) over the device time under the ``attention_latent`` scope in
the traced slice.  Projections are outside the scope."""

from benchmark.harness import latent_decoder_flops as flops
from benchmark.harness import scope_shares

NAME = "mla_core_roofline"
UNIT = "%"
LAYER = "kernels"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(record):
    latent = record.get("latent")
    steps = scope_shares.traced_train_steps(record)
    if not latent or not steps:
        return None
    mc = record["model_cfg"]
    seqs = steps * latent["sequences_per_step"]
    return scope_shares.roofline_share(
        record, ("attention_latent",),
        seqs * flops.cores_flops_step(mc, latent["seq_len"]),
        seqs * flops.cores_bytes_step(mc, latent["seq_len"]))
