"""The pool step's share of its roofline at the largest bucket: the larger
of analytic FLOPs over peak FLOP/s and analytic bytes over peak bytes/s
(benchmark/harness/flops.py, device.py), over the measured device time
per flush.  The step moves ~40x more time's worth of bytes than of
FLOPs, so the bound is bandwidth."""

from benchmark.harness import readers

NAME = "pool_step_roofline"
UNIT = "%"
LAYER = "pool step"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "ticks_per_s"
read = readers.pool_step_roofline
