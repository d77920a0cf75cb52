"""How many times a train step runs an attention core's forward kernel a
layer: executions of the device operations named ``flash_fwd*`` /
``sparse_fwd*`` inside a whole execution of ``jit_train_step`` in the
traced slice, over the model's layers; of the slice's whole executions,
the median.

1.0 where the backward reads what the forward pass kept; 2.0 where a
block's recomputation runs the core again only to remake its output and
row statistics.  Whole steps alone (a step the slice's edge cuts holds
its kernels unevenly: forward first, the replay late) and their median
(an entry of the ``XLA Modules`` line that is no step of the loop does
not move it), so the reading is a count over the layers.  A program
whose step runs neither kernel (another family, the non-kernel path)
gives None.
"""

import gzip
import re
import statistics

from benchmark.harness.program_spans import (
    MODULES_LINE, OP_LINES, _event, _event_metadata, _fields, _line, _plane,
    short_hlo_name)
from benchmark.harness.scope_shares import STEP_PROGRAM
from benchmark.harness.trace_reduce import DEVICE_PLANE_PREFIX, SLICE_SPAN

NAME = "attention_core_fwd_runs_per_layer"
UNIT = "runs/layer"
LAYER = "train step"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

_CORE_FWD = re.compile(r"^(flash_fwd|sparse_fwd)(\.\d+)?$")


def core_runs_and_steps(path):
    """(starts of the core-forward executions, (start, duration) of the
    step program's executions, the slice's bounds), in nanoseconds, off
    the first device plane; None where the trace lacks one of them."""
    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as fh:
        data = memoryview(fh.read())
    bounds = steps = cores = None
    for f, v in _fields(data):
        if f != 1:  # XSpace.planes
            continue
        plane = _plane(v)
        host = plane["name"].startswith("/host:")
        if not host and not (plane["name"].startswith(DEVICE_PLANE_PREFIX)
                             and steps is None):
            continue
        names = {k: n for k, (n, _) in _event_metadata(plane).items()}
        if not host:
            steps, cores = [], []
        for raw in plane["lines"]:
            line_name, t0, events = _line(raw)
            if not host and line_name not in OP_LINES + (MODULES_LINE,):
                continue
            for ev in events:
                meta, offset, dur = _event(ev)
                name = names.get(meta, "")
                if host:
                    if name == SLICE_SPAN:
                        bounds = (t0 + offset / 1e3,
                                  t0 + (offset + dur) / 1e3)
                elif line_name == MODULES_LINE:
                    if name.startswith(STEP_PROGRAM + "("):
                        steps.append((t0 + offset / 1e3, dur / 1e3))
                elif _CORE_FWD.match(short_hlo_name(name)):
                    cores.append(t0 + offset / 1e3)
    if not bounds or not steps or not cores:
        return None
    return cores, steps, bounds


def runs_per_layer(cores, steps, bounds, n_layers):
    lo, hi = bounds
    counts = [sum(1 for t in cores if s <= t < s + d)
              for s, d in steps if s >= lo and s + d <= hi]
    if not counts or not n_layers:
        return None
    return statistics.median(counts) / n_layers or None


def read(record):
    tracer = record.get("tracer")
    path = tracer.trace_file() if tracer is not None else None
    layout = getattr(record.get("model_cfg"), "layer_layout", None)
    if path is None or not layout:
        return None
    found = core_runs_and_steps(path)
    return found and runs_per_layer(*found, len(layout))
