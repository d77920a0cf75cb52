"""Roofline share of the chunked scan over matrix-valued state: the
least time the chip could take for the scan's four products in its
published chunked form (over the causal pairs inside a chunk;
harness/hybrid_decoder_flops.py), forward and backward, and for the
least traffic of its inputs, outputs and carried states, over the device
time under the ``ssd_scan`` scope in the traced slice.  The positions
are the ones the program counted (``ssd_positions_total`` by layer over
the window's training passes), not the configuration's.  The
recomputation's repeat of the forward, the decays' exponentials and a
whole block multiplied where half is masked are in the time and not in
the operations."""

from benchmark.harness import hybrid_decoder_flops as flops
from benchmark.harness import scope_shares

NAME = "ssd_scan_roofline"
UNIT = "%"
LAYER = "kernels"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(record):
    hybrid = record.get("hybrid")
    steps = scope_shares.traced_train_steps(record)
    if not hybrid or not steps:
        return None
    positions = steps * sum(hybrid["scan_positions_per_train_step"])
    if not positions:
        return None
    mc = record["model_cfg"]
    sizes = (mc.ssm_heads, mc.ssm_head_dim, mc.ssm_state, mc.ssm_chunk)
    return scope_shares.roofline_share(
        record, ("ssd_scan",), flops.scan_flops_step(positions, *sizes),
        flops.scan_bytes_step(positions, *sizes))
