"""Peak device memory in use on the fullest chip, whole process (a guard,
not a lever)."""

from benchmark.harness import readers

NAME = "peak_hbm_mb"
UNIT = "MB"
LAYER = "device"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = {"tick_p50_ms": "peak_hbm_mb",
         "ticks_per_s": "backlog_peak_hbm_mb",
         "train_samples_per_s": "train_peak_hbm_mb"}
read = readers.peak_hbm_mb
