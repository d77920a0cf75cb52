"""Share of an epoch's wall time inside its validation pass's loop and
drain: ``run_s`` of the ``eval`` part of each ``train.epoch`` record over
the record's ``total_s``, the median over the untraced window's
epochs."""

from benchmark.harness import epoch_account

NAME = "eval_pass_share"
UNIT = "%"
LAYER = "epoch loop"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "train_samples_per_s"
read = epoch_account.reader(NAME)
