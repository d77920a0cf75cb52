"""Roofline share of the hyper-connections, by bytes: the least time the
chip could take to read the four-lane stream once and write it once a
sublayer forward, and to read it and the written stream's gradient and
write the stream's gradient backward (with the coefficient product's
operations beside them; harness/latent_decoder_flops.py counts the
mechanism, whatever implements it), over the device time under the
``hyper_conn`` scope in the traced slice."""

from benchmark.harness import latent_decoder_flops as flops
from benchmark.harness import scope_shares

NAME = "hc_mix_roofline"
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
        record, ("hyper_conn",),
        seqs * flops.mixing_flops_step(mc, latent["seq_len"]),
        seqs * flops.mixing_bytes_step(mc, latent["seq_len"]))
