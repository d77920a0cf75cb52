"""What the compiled train step's temporaries take on the device
(gradients, activations kept for the backward pass, scratch), by the
compiler's own analysis of the program the window ran: ``temp_bytes`` of
the ``train_step`` record in the program's compile ledger
(``harness/compile_account.py``).  ``train_peak_hbm_mb`` is
``memory_stats``' peak and does not see them."""

from benchmark.harness import compile_account

NAME = "train_step_temp_hbm_mb"
UNIT = "MB"
LAYER = "device"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "train_samples_per_s"
read = compile_account.memory_mb("temp_bytes")
