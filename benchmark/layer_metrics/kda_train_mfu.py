"""Model FLOP/s utilization of the cell whose layers mix the delta rule
with a decay a channel and latent attention (Kimi-Linear): analytic
forward + backward operations a sequence (harness/kda_decoder_flops.py:
each delta-rule layer's projections and its walk in the published
chunked form, the latent layer's projections and its core over the
causal triangle at 192 / 128, the dense layer's MLP, the expert layers'
router, shared expert and held routed experts for the pairs the window's
train steps held, the head over the slice; recomputed operations not
counted) times train_samples_per_s over the chips' peak bf16 FLOP/s.
End to end, validation passes and epoch boundaries included: the share
of the whole step, not a kernel's roofline share.  Reads the ``kda``
record only this family's driver writes."""

from benchmark.harness import kda_decoder_flops as flops
from benchmark.harness.device import peaks_for

NAME = "kda_train_mfu"
UNIT = "%"
LAYER = "train step"
BETTER = "higher"
SOURCE = "host_clock"
MOVES = "train_samples_per_s"


def read(record):
    kda = record.get("kda")
    rate = record["end_to_end"].get("train_samples_per_s")
    if not kda or not rate or record["device"]["platform"] != "tpu":
        return None
    mc = record["model_cfg"]
    tokens = kda["seq_len"] * kda["sequences_per_step"]
    layers = max(len(kda["pairs_per_train_step"]), 1)
    pairs_per_token = sum(kda["pairs_per_train_step"]) / layers / tokens
    per_sequence = flops.train_flops_per_sequence(
        mc, kda["seq_len"], pairs_per_token)
    peak_flops, _ = peaks_for(record["device"]["kind"])
    return 100.0 * per_sequence * rate / (
        peak_flops * record["device"]["count"])
