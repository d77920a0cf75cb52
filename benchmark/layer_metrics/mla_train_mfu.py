"""Model FLOP/s utilization of the latent-attention cell under a plain
residual (Moonlight): analytic forward + backward operations a sequence
(harness/mla_decoder_flops.py: every layer's query product as the
configuration states it, direct here, the key-value and output products,
the core over the causal triangle at 192 / 128, the dense layer's MLP,
the expert layers' router, two shared experts and held routed experts
for the pairs the window's train steps held, the head over the slice;
the balance term is VPU work and not counted; recomputed operations not
counted) times train_samples_per_s over the chips' peak bf16 FLOP/s.
End to end, validation passes and epoch boundaries included: the share
of the whole step, not a kernel's roofline share.  Reads the ``mla``
record only this cell's driver writes."""

from benchmark.harness import mla_decoder_flops as flops
from benchmark.harness.device import peaks_for

NAME = "mla_train_mfu"
UNIT = "%"
LAYER = "train step"
BETTER = "higher"
SOURCE = "host_clock"
MOVES = "train_samples_per_s"


def read(record):
    mla = record.get("mla")
    rate = record["end_to_end"].get("train_samples_per_s")
    if not mla or not rate or record["device"]["platform"] != "tpu":
        return None
    mc = record["model_cfg"]
    tokens = mla["seq_len"] * mla["sequences_per_step"]
    layers = max(len(mla["pairs_per_train_step"]), 1)
    pairs_per_token = sum(mla["pairs_per_train_step"]) / layers / tokens
    per_sequence = flops.train_flops_per_sequence(
        mc, mla["seq_len"], pairs_per_token)
    peak_flops, _ = peaks_for(record["device"]["kind"])
    return 100.0 * per_sequence * rate / (
        peak_flops * record["device"]["count"])
