"""The arithmetic of the readers of the trainer's epoch account.

``Trainer.fit`` leaves one ``train.epoch`` record an epoch in the
process's epoch ring (``fmda_tpu/train/epoch_account.py``: the epoch's
parts on the host clock, tiling it).  The readers here take the
**window's** epochs out of the ring and give the median over them, so the
numbers stand beside the untraced headline: no slice, no annotation
count, no profiler.

The window's epochs are the first warm epochs (``mark_warm`` ends the
drivers' set-up) whose ends fall within ``record["window_s"]`` of the
first one's start: the set-up's epochs are cold and a traced run's tail
begins after the reference check, further than an epoch beyond the
window.  A commit whose trainer writes no record gives None in every
reader; a ring that has let go of records gives None and a warning,
never a number from fewer epochs.
"""

from __future__ import annotations

import json
import statistics
import sys
from typing import Callable, Dict, List, Optional, Sequence

KIND = "train.epoch"
PARTS = ("open_s", "run_s", "publish_s")
_MEMO = "_epoch_account"


def _say(obj: Dict) -> None:
    print(json.dumps(obj), file=sys.stderr, flush=True)


def ring_records():
    """``(records, emitted)`` of the process's epoch ring, oldest first
    and the count ever emitted; None where the program has no such ring
    (a commit before the account)."""
    try:
        from fmda_tpu.obs.events import default_epoch_log
    except ImportError:
        return None
    ring = default_epoch_log()
    return [e for e in ring.tail() if e.get("kind") == KIND], ring.emitted


def window_epochs(records: Sequence[Dict], emitted: int,
                  window_s: float) -> Optional[List[Dict]]:
    """The window's epochs of ``records`` (oldest first; ``emitted`` ever
    written to the ring they come from), or None with a warning where
    the ring holds fewer records than were written."""
    if emitted > len(records):
        _say({"warning": (
            f"epoch account: the ring holds {len(records)} of {emitted} "
            "records, so the window's epochs cannot be told from the "
            "rest: no reading")})
        return None
    warm = [r for r in records if r.get("warm")]
    if not warm:
        return None
    limit = warm[0]["start"] + window_s
    picked = []
    for r in warm:
        if r["end"] > limit:
            break
        picked.append(r)
    return picked or None


def _per_step_ms(phase: str) -> Callable[[Dict], Optional[float]]:
    def of(epoch):
        part = epoch.get(phase)
        return None if part is None else (
            1e3 * part["run_s"] / part["steps"])
    return of


def _eval_share(epoch):
    part = epoch.get("eval")
    return None if part is None else (
        100.0 * part["run_s"] / epoch["total_s"])


def _turnaround_share(epoch):
    running = sum(epoch[p]["run_s"] for p in ("train", "eval")
                  if p in epoch)
    return 100.0 * (epoch["total_s"] - running) / epoch["total_s"]


#: each metric as a function of one epoch's record (None where the
#: epoch has no such pass)
METRICS = {
    "train_pass_ms_per_step": _per_step_ms("train"),
    "eval_pass_ms_per_step": _per_step_ms("eval"),
    "eval_pass_share": _eval_share,
    "epoch_turnaround_share": _turnaround_share,
}


def medians(epochs: Sequence[Dict]) -> Dict[str, Optional[float]]:
    """Each metric's median over the epochs that have it."""
    out = {}
    for name, of in METRICS.items():
        values = [v for v in map(of, epochs) if v is not None]
        out[name] = statistics.median(values) if values else None
    return out


def _parts_line(epochs: Sequence[Dict], rec: Dict) -> Dict:
    """What the run's stderr says of the account: the epochs picked,
    each part's median, and the rate the records alone give beside the
    driver's headline."""
    def med(of):
        return statistics.median([of(e) for e in epochs])

    parts = {"fit_setup_s": med(lambda e: e["fit_setup_s"]),
             "epoch_end_s": med(lambda e: e["epoch_end_s"]),
             "total_s": med(lambda e: e["total_s"])}
    for phase in ("train", "eval"):
        have = [e[phase] for e in epochs if phase in e]
        if have:
            parts[phase] = {
                k: statistics.median([p[k] for p in have])
                for k in PARTS + ("steps", "calls")}
    total = sum(e["total_s"] for e in epochs)
    line = {"epochs_picked": len(epochs), "window_s": rec["window_s"],
            "sum_total_s": total, "median_parts": parts,
            "compiles": sum(e.get("compiles") or 0 for e in epochs)}
    per_epoch = rec.get("valid_windows_per_epoch",
                        rec.get("valid_sequences_per_epoch"))
    rate = rec.get("end_to_end", {}).get("train_samples_per_s")
    if per_epoch and rate:
        line["epochs_by_headline"] = rate * rec["window_s"] / per_epoch
        line["samples_per_s_by_records"] = per_epoch * len(epochs) / total
        line["samples_per_s_headline"] = rate
    return line


def account(rec: Dict) -> Optional[Dict[str, Optional[float]]]:
    """The four medians of the run's window, computed once a run (and
    said once on stderr, with the parts behind them)."""
    if _MEMO not in rec:
        rec[_MEMO] = None
        ring = ring_records()
        if ring is not None and rec.get("window_s"):
            epochs = window_epochs(*ring, rec["window_s"])
            if epochs:
                rec[_MEMO] = medians(epochs)
                _say({"epoch_account": _parts_line(epochs, rec)})
    return rec[_MEMO]


def reader(name: str) -> Callable[[Dict], Optional[float]]:
    def read(rec):
        got = account(rec)
        return None if got is None else got[name]
    return read
