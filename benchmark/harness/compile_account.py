"""The readers of the program's compile account.

The program's compile ledger (``fmda_tpu/obs/device.py``
``CompileLedger``) keeps one record a compile of a tracked program —
what the compile was made of (``trace_s``, ``lower_s``,
``backend_compile_s``, the persistent cache's ``hit`` or ``miss`` and
retrieval time, and ``rest_s``: the first execution and the dispatch)
and, where the trainer asked at ``mark_warm``, what the compiled program
holds on the device by the compiler's own ``memory_analysis()`` — a
table of what jax compiled outside any tracked program, and
``Trainer.fit`` writes each epoch's share of all that into its
``train.epoch`` record (``compile_parts``).  This file says the whole of
it once a run on stderr, as ``harness/epoch_account.py`` says the
epoch's parts, and gives the two readers of the step's memory their
numbers.

The records outlive the trainer they describe, which is gone by the time
a reader runs.  A commit whose program has no such ledger (or no
``memory`` in its records) gives None in every reader and says no line.

No per-layer metric can move ``setup_s`` while ``run.py`` hands
``layers.read_all`` the driver's ``END_TO_END`` alone (``setup_s`` is
joined into ``units`` two lines above, not into that): the set-up's
parts are therefore in the line, and the metrics they would make
(``setup_trace_s``, ``setup_backend_compile_s``,
``setup_cache_hit_share``) wait for that one line (PERF.md section 7).
"""

from __future__ import annotations

import json
import sys
from typing import Callable, Dict, List, Optional

from benchmark.harness import epoch_account

TRAIN_PROGRAM = "train_step"
PROGRAM_PARTS = ("compile_s", "trace_s", "lower_s", "backend_compile_s",
                 "rest_s", "cache", "cache_retrieval_s",
                 "compile_time_saved_s", "unexpected")
_MEMO = "_compile_account"


def _say(obj: Dict) -> None:
    print(json.dumps(obj), file=sys.stderr, flush=True)


def process_ledger():
    """The process's compile ledger, or None on a commit whose ledger
    keeps no compile records."""
    try:
        from fmda_tpu.obs.device import default_ledger
    except ImportError:
        return None
    ledger = default_ledger()
    return ledger if hasattr(ledger, "compile_records") else None


def window_program(records: List[Dict], program: str) -> Optional[Dict]:
    """The compile record of the ``program`` the window ran: the newest
    one that was asked what it holds.  The drivers call ``mark_warm`` on
    the window's trainer and on no other, and ``mark_warm`` asks the
    programs ``fit`` had run (of each kind the single or the grouped
    one); the programs the comparisons with the reference compile
    afterwards are never asked."""
    for rec in reversed(records):
        if rec.get("program") == program and rec.get("memory"):
            return rec
    return None


def _by_program(records: List[Dict]) -> Dict[str, Dict]:
    """The line's table: each program's compiles in order, the parts of
    each."""
    out: Dict[str, Dict] = {}
    for rec in records:
        row = out.setdefault(rec["program"], {"compiles": 0, "each": []})
        row["compiles"] += 1
        row["each"].append({k: rec.get(k) for k in PROGRAM_PARTS})
    return out


def _epochs(ring) -> Dict:
    """The trainer's own account of the run's epochs (``ring``:
    ``epoch_account.ring_records()``): the set-up's (those before
    ``mark_warm``), ``total_s`` beside ``compile_parts``, and how many
    of the warm ones compiled anything."""
    epochs = ring[0] if ring else []
    warm = [e for e in epochs if e.get("warm")]
    return {
        "setup_epochs": [
            {"epoch": e["epoch"], "total_s": e["total_s"],
             "fit_setup_s": e["fit_setup_s"], "compiles": e.get("compiles"),
             "compile_parts": e.get("compile_parts")}
            for e in epochs if not e.get("warm")],
        "warm_epochs": len(warm),
        "warm_epochs_that_compiled": sum(
            1 for e in warm if any((e.get("compile_parts") or {}).values())),
    }


def account(rec: Dict) -> Optional[Dict]:
    """The run's compile account, read once a run and said once on
    stderr; None where the program keeps none."""
    if _MEMO not in rec:
        rec[_MEMO] = None
        ledger = process_ledger()
        if ledger is not None:
            records = ledger.compile_records()
            held = {name: (window_program(records, name) or {}).get("memory")
                    for name in (TRAIN_PROGRAM, "eval_step")}
            if held[TRAIN_PROGRAM] is not None:
                rec[_MEMO] = {"train_step_memory": held[TRAIN_PROGRAM]}
            _say({"compile_account": {
                "programs": _by_program(records),
                "untracked": ledger.untracked(),
                **_epochs(epoch_account.ring_records()),
                "window_memory": held,
            }})
    return rec[_MEMO]


def memory_mb(key: str) -> Callable[[Dict], Optional[float]]:
    """A reader of one number of the train step's memory, in MB (1e6
    bytes, as ``train_peak_hbm_mb``)."""
    def read(rec):
        got = account(rec)
        return None if got is None else got["train_step_memory"][key] / 1e6
    return read
