"""A training pass's wall time over the steps it ran, by the trainer's own
account of the epoch: ``run_s`` of the ``train`` part of each
``train.epoch`` record (the loop's first pull to the return of the
pass's ``device_get``) over its ``steps``, the median over the untraced
window's epochs.  Where the device paces, the device step plus the
pipe's fill and drain; it needs no slice and no annotation count."""

from benchmark.harness import epoch_account

NAME = "train_pass_ms_per_step"
UNIT = "ms/step"
LAYER = "epoch loop"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "train_samples_per_s"
read = epoch_account.reader(NAME)
