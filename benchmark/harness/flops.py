"""Operations and bytes the algorithms need, from their shapes.

``train_flops_per_window`` is ``bench.py``'s ``model_flops_per_step``
arithmetic (copied; the original is listed in PERF.md for a later PR to
delete), per window instead of per batch.  Matrix multiplications only:
the gates' elementwise work is VPU noise beside them.
"""

from __future__ import annotations


def train_flops_per_window(seq: int, features: int, hidden: int,
                           classes: int, *, bidirectional: bool = True,
                           cell: str = "gru") -> float:
    """Forward + backward of the recurrent classifier on one window.  Per
    direction the input projection is 2*T*F*3H and, for the gru, the
    recurrence T * 2*H*3H (the ssm's transition is elementwise: no
    matrix); the head is 2*3H*C; a train step is ~3x the forward
    (backward ~2x)."""
    dirs = 2 if bidirectional else 1
    per_dir = 2 * seq * features * 3 * hidden
    if cell != "ssm":
        per_dir += seq * 2 * hidden * 3 * hidden
    fwd = dirs * per_dir + 2 * 3 * hidden * classes
    return 3.0 * fwd


def pool_step_flops(cell: str, lanes: int, features: int, hidden: int,
                    classes: int) -> float:
    """One pool step over ``lanes`` lanes: the input projection
    (2*F*3H a lane), for the gru the recurrent product (2*H*3H), and the
    head (2*3H*C)."""
    per_lane = 2 * features * 3 * hidden + 2 * 3 * hidden * classes
    if cell != "ssm":
        per_lane += 2 * hidden * 3 * hidden
    return float(lanes * per_lane)


def pool_step_bytes(cell: str, lanes: int, features: int, hidden: int,
                    classes: int, window: int, itemsize: int = 4) -> float:
    """HBM bytes one pool step has to move, at the least: per lane the
    row in, the two normalisation vectors, the carry read and written
    (one H-vector for the gru, three for the ssm), for the gru one ring
    entry written and the whole trailing window read for the pooled
    head, and the probabilities out; the weights once."""
    carry = 3 if cell == "ssm" else 1
    per_lane = (features * 4            # the row (float32 from the host)
                + 2 * features * 4      # x_min, x_range
                + 2 * carry * hidden * itemsize
                + classes * 4 + 2 * 4)  # probabilities; slot and pos
    if cell != "ssm":
        per_lane += (1 + window) * hidden * itemsize
    weights = (3 * hidden * features + 3 * hidden
               + (0 if cell == "ssm" else 3 * hidden * hidden + 3 * hidden)
               + 3 * hidden * classes + classes) * itemsize
    return float(lanes * per_lane + weights)
