"""Where an epoch's wall time went: one ``train.epoch`` record an epoch.

``Trainer.fit`` / ``fit_multi`` read the host clock where a pass's
parts begin and end — never inside the step loop — and hand the reads
here.  The parts tile the epoch: each begins where the one before it
ended, so they sum to ``total_s`` to the clock's resolution.  Between a
pass's drain and the next pass's first call the device has nothing
queued, so every part but a pass's ``run_s`` is time the device idles
(docs/observability.md "Spans and scopes": the same boundaries carry
the ``fit_setup`` / ``*_pass_open`` / ``*_pass_drain`` /
``*_pass_publish`` / ``fit_epoch_end`` spans on the profiler's clock).

Record fields (kind ``train.epoch`` in
:func:`fmda_tpu.obs.events.default_epoch_log`, seconds of
``time.perf_counter``):

- ``epoch``: epochs the trainer had finished when this one began (what
  the epoch's spans carry as their ``epoch`` argument);
- ``start`` / ``end`` / ``total_s``: the epoch, from the entry of the
  ``fit`` call for a call's first epoch, else from the last epoch's end;
- ``fit_setup_s``: the call's set-up (split, rng, state, the norm
  check), in the first epoch of a call; 0.0 in its later ones;
- ``train`` / ``eval`` (a pass that ran steps: no ``eval`` without
  validation chunks, and a pass that met no batches leaves its time to
  the part after it): ``open_s`` (cache lookup, ``zero_totals``, up to the loop's
  first pull), ``run_s`` (the loop and its drain: first pull to the
  return of ``device_get``), ``publish_s`` (``task.publish`` and
  ``epoch_metrics``), ``steps``, ``calls``, ``cache`` (``"hit"`` /
  ``"miss"`` of the placed-batch cache, None where none was asked),
  and what the task's ``publish`` returned for the pass (a token task
  whose layers declare loss terms: each term's sum over its layers, a
  step, e.g. ``seq_aux_loss``);
- ``epoch_end_s``: history, ``train_epoch_seconds``, the log line;
- ``warm``: ``mark_warm`` had been called when the epoch ended;
- ``compiles``: programs the trainer's tracked steps compiled during
  the epoch (None where jax's cache probe is unavailable);
- ``compile_parts``: what jax compiled in the process during the epoch,
  tracked programs and everything else together, by difference of the
  compile ledger's running totals (``obs/device.py``
  ``CompileLedger.compile_parts_total``): ``trace_s``, ``lower_s``,
  ``backend_compile_s``, ``cache_hits``, ``cache_misses``,
  ``cache_retrieval_s``.  All zero in an epoch that compiled nothing.
  The three times lie inside ``total_s`` (on the calling thread: inside
  ``fit_setup_s`` and the passes' ``run_s``).
"""

from __future__ import annotations

from typing import Dict, Optional

from fmda_tpu.obs.events import default_epoch_log

EVENT_KIND = "train.epoch"

#: the keys of a record's ``compile_parts``, in the order of
#: ``CompileLedger.compile_parts_total()``
COMPILE_PARTS = ("trace_s", "lower_s", "backend_compile_s", "cache_hits",
                 "cache_misses", "cache_retrieval_s")


def emit_epoch(
    epoch: int,
    t_start: float,
    t_epoch: float,
    passes: Dict[str, Dict],
    t_end: float,
    *,
    warm: bool,
    compiles: Optional[int],
    compile_parts: Dict[str, float],
) -> Dict[str, object]:
    """Assemble one epoch's record from its clock reads and append it to
    the process's epoch ring (through the ring's ``mirror``, to an
    application's ``/events``).  The reads: ``t_start`` (entry of
    ``fit``, or the last epoch's end), ``t_epoch`` (set-up done), each
    pass's account by phase in the order run (``t_run``, ``t_drained``,
    ``t_published``, ``steps``, ``calls`` and perhaps ``cache``:
    ``Trainer._run_batches``), ``t_end``."""
    record: Dict[str, object] = {
        "epoch": epoch, "start": t_start, "end": t_end,
        "fit_setup_s": t_epoch - t_start}
    t = t_epoch
    for phase, account in passes.items():
        if not account["steps"]:
            continue
        record[phase] = {
            "open_s": account["t_run"] - t,
            "run_s": account["t_drained"] - account["t_run"],
            "publish_s": account["t_published"] - account["t_drained"],
            "steps": account["steps"],
            "calls": account["calls"],
            "cache": account.get("cache"),
            **(account.get("published") or {}),
        }
        t = account["t_published"]
    record.update(epoch_end_s=t_end - t, total_s=t_end - t_start,
                  warm=warm, compiles=compiles, compile_parts=compile_parts)
    default_epoch_log().emit(EVENT_KIND, **record)
    return record
