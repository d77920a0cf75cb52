"""Share of an epoch's wall time outside both passes' loops: ``total_s``
less the ``run_s`` of the ``train`` and ``eval`` parts of each
``train.epoch`` record (``fit``'s set-up, each pass's opening and
publishing, the epoch's end), over ``total_s``; the median over the
untraced window's epochs.  All of it is time in which the device has
nothing queued."""

from benchmark.harness import epoch_account

NAME = "epoch_turnaround_share"
UNIT = "%"
LAYER = "epoch loop"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "train_samples_per_s"
read = epoch_account.reader(NAME)
