"""Roofline share of the chunked walk of the delta rule with a decay a
channel: the least time the chip could take for the walk's products in
its published chunked form (over the causal pairs inside a chunk, the
solve counted as its products; harness/kda_decoder_flops.py), forward
and backward, and for the least traffic of its inputs, outputs and
carried states, over the device time under the ``kda_scan`` scope in the
traced slice.  The positions are the ones the program counted
(``kda_positions_total`` by layer over the window's training passes),
not the configuration's.  The recomputation's repeats of the forward,
the decays' exponentials, the pairwise decays taken on the vector unit
and a whole block multiplied where half is masked are in the time and
not in the operations.  Reads the ``kda`` record only this family's
driver writes."""

from benchmark.harness import kda_decoder_flops as flops
from benchmark.harness import scope_shares

NAME = "kda_scan_roofline"
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
    positions = steps * sum(kda["scan_positions_per_train_step"])
    if not positions:
        return None
    mc = record["model_cfg"]
    sizes = (mc.kda_heads, mc.kda_head_dim, mc.kda_chunk)
    return scope_shares.roofline_share(
        record, ("kda_scan",), flops.scan_flops_step(positions, *sizes),
        flops.scan_bytes_step(positions, *sizes))
