"""Model FLOP/s utilization of the cell whose layers mix the gated delta
rule and full attention (Olmo-Hybrid): analytic forward + backward
operations a sequence (harness/gdn_decoder_flops.py: each delta-rule
layer's projections and its walk in the chunked form with a scalar
decay, the attention layer's projections and its core over the causal
triangle, every layer's dense MLP, the head over the slice; recomputed
operations not counted) times train_samples_per_s over the chips' peak
bf16 FLOP/s.  End to end, validation passes and epoch boundaries
included: the share of the whole step, not a kernel's roofline share.
Reads the ``gdn`` record only this family's driver writes."""

from benchmark.harness import gdn_decoder_flops as flops
from benchmark.harness.device import peaks_for

NAME = "gdn_train_mfu"
UNIT = "%"
LAYER = "train step"
BETTER = "higher"
SOURCE = "host_clock"
MOVES = "train_samples_per_s"


def read(record):
    gdn = record.get("gdn")
    rate = record["end_to_end"].get("train_samples_per_s")
    if not gdn or not rate or record["device"]["platform"] != "tpu":
        return None
    per_sequence = flops.train_flops_per_sequence(
        record["model_cfg"], gdn["seq_len"])
    peak_flops, _ = peaks_for(record["device"]["kind"])
    return 100.0 * per_sequence * rate / (
        peak_flops * record["device"]["count"])
