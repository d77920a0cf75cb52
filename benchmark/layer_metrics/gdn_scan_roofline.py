"""Roofline share of the chunked walk of the delta rule with one decay a
head: the least time the chip could take for the walk's products in its
chunked form with a scalar decay (over the causal pairs inside a chunk,
the solve counted as its products, keys and values at their own widths;
harness/gdn_decoder_flops.py), forward and backward, and for the least
traffic of its inputs, outputs and carried states, over the device time
under the ``gdn_scan`` scope in the traced slice.  The positions are the
ones the program counted (``gdn_positions_total`` by layer over the
window's training passes), not the configuration's.  The recomputation's
repeats of the forward, the decays' exponentials, the solve's halving on
the vector unit and a whole block multiplied where half is masked are in
the time and not in the operations.  Reads the ``gdn`` record only this
family's driver writes."""

from benchmark.harness import gdn_decoder_flops as flops
from benchmark.harness import scope_shares

NAME = "gdn_scan_roofline"
UNIT = "%"
LAYER = "kernels"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(record):
    gdn = record.get("gdn")
    steps = scope_shares.traced_train_steps(record)
    if not gdn or not steps:
        return None
    positions = steps * sum(gdn["scan_positions_per_train_step"])
    if not positions:
        return None
    mc = record["model_cfg"]
    sizes = (mc.gdn_heads, mc.gdn_key_dim, mc.gdn_value_dim, mc.gdn_chunk)
    return scope_shares.roofline_share(
        record, ("gdn_scan",), flops.scan_flops_step(positions, *sizes),
        flops.scan_bytes_step(positions, *sizes))
