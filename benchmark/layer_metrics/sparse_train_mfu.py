"""Model FLOP/s utilization of the learned-sparse decoder cell: analytic
forward + backward operations a sequence (harness/sparse_decoder_flops.py:
projections, the indexer once, the cores over the picked pairs, the
router, the held experts' part from the pairs the program counted, the
head over the slice; recomputed operations not counted) times
train_samples_per_s over the chips' peak bf16 FLOP/s.  End to end,
validation passes and epoch boundaries included; not a kernel's roofline
share."""

from benchmark.harness import sparse_decoder_flops as flops
from benchmark.harness.device import peaks_for

NAME = "sparse_train_mfu"
UNIT = "%"
LAYER = "train step"
BETTER = "higher"
SOURCE = "host_clock"
MOVES = "train_samples_per_s"


def read(record):
    sparse = record.get("sparse")
    rate = record["end_to_end"].get("train_samples_per_s")
    if not sparse or not rate or record["device"]["platform"] != "tpu":
        return None
    mc = record["model_cfg"]
    tokens_per_step = sparse["seq_len"] * sparse["sequences_per_step"]
    pairs_per_token = (sum(sparse["pairs_per_train_step"])
                       / len(mc.layer_layout) / tokens_per_step)
    per_sequence = flops.train_flops_per_sequence(
        mc, sparse["seq_len"], pairs_per_token)
    peak_flops, _ = peaks_for(record["device"]["kind"])
    return 100.0 * per_sequence * rate / (
        peak_flops * record["device"]["count"])
