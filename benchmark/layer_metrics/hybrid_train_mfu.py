"""Model FLOP/s utilization of the hybrid decoder cell: analytic forward
+ backward operations a sequence (harness/hybrid_decoder_flops.py: every
layer's projections and dense MLP, the scans in their published chunked
form, the attention cores over the causal triangle, the tied head over
the slice; recomputed operations not counted) times train_samples_per_s
over the chips' peak bf16 FLOP/s.  End to end, validation passes and
epoch boundaries included; not a kernel's roofline share."""

from benchmark.harness import hybrid_decoder_flops as flops
from benchmark.harness.device import peaks_for

NAME = "hybrid_train_mfu"
UNIT = "%"
LAYER = "train step"
BETTER = "higher"
SOURCE = "host_clock"
MOVES = "train_samples_per_s"


def read(record):
    hybrid = record.get("hybrid")
    rate = record["end_to_end"].get("train_samples_per_s")
    if not hybrid or not rate or record["device"]["platform"] != "tpu":
        return None
    per_sequence = flops.train_flops_per_sequence(
        record["model_cfg"], hybrid["seq_len"])
    peak_flops, _ = peaks_for(record["device"]["kind"])
    return 100.0 * per_sequence * rate / (
        peak_flops * record["device"]["count"])
