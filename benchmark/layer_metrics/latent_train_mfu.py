"""Model FLOP/s utilization of the latent-attention decoder cell:
analytic forward + backward operations a sequence
(harness/latent_decoder_flops.py: every layer's latent projections, core
over the causal triangle at 192 / 128 and two coefficient products, the
dense layer's MLP, the expert layers' router, shared expert and held
routed experts for the pairs the window's train steps held, the head
over the slice; recomputed operations not counted) times
train_samples_per_s over the chips' peak bf16 FLOP/s.  End to end,
validation passes and epoch boundaries included: the share of the whole
step, not a kernel's roofline share."""

from benchmark.harness import latent_decoder_flops as flops
from benchmark.harness.device import peaks_for

NAME = "latent_train_mfu"
UNIT = "%"
LAYER = "train step"
BETTER = "higher"
SOURCE = "host_clock"
MOVES = "train_samples_per_s"


def read(record):
    latent = record.get("latent")
    rate = record["end_to_end"].get("train_samples_per_s")
    if not latent or not rate or record["device"]["platform"] != "tpu":
        return None
    mc = record["model_cfg"]
    tokens = latent["seq_len"] * latent["sequences_per_step"]
    layers = max(len(latent["pairs_per_train_step"]), 1)
    pairs_per_token = sum(latent["pairs_per_train_step"]) / layers / tokens
    per_sequence = flops.train_flops_per_sequence(
        mc, latent["seq_len"], pairs_per_token)
    peak_flops, _ = peaks_for(record["device"]["kind"])
    return 100.0 * per_sequence * rate / (
        peak_flops * record["device"]["count"])
