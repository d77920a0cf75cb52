"""Share of the traced slice in which no operation ran on the device: how
far the host holds the chip back."""

from benchmark.harness import readers

NAME = "device_idle_share"
UNIT = "%"
LAYER = "device"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = {"tick_p50_ms": "device_idle_share",
         "ticks_per_s": "backlog_device_idle_share",
         "train_samples_per_s": "train_device_idle_share"}
read = readers.device_idle_share
