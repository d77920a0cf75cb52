"""What the compiled train step reserves on the device while it runs, by
the compiler's own analysis of the program the window ran: arguments +
outputs - aliased (donated) + temporaries + code, ``reserved_bytes`` of
the ``train_step`` record in the program's compile ledger
(``harness/compile_account.py``).  A chip has 16,000 MB; what the
process holds beside the step (the placed batches) is in
``train_peak_hbm_mb``."""

from benchmark.harness import compile_account

NAME = "train_step_reserved_hbm_mb"
UNIT = "MB"
LAYER = "device"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "train_samples_per_s"
read = compile_account.memory_mb("reserved_bytes")
