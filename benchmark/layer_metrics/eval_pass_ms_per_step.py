"""A validation pass's wall time over the steps it ran: ``run_s`` of the
``eval`` part of each ``train.epoch`` record over its ``steps``, the
median over the untraced window's epochs (no traced slice holds an eval
step, so this is the one reading of one)."""

from benchmark.harness import epoch_account

NAME = "eval_pass_ms_per_step"
UNIT = "ms/step"
LAYER = "epoch loop"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "train_samples_per_s"
read = epoch_account.reader(NAME)
