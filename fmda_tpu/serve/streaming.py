"""Streaming inference with carried hidden state — the O(1)-per-tick path.

The reference (and the flagship bidirectional :class:`Predictor`) re-scan a
full window per tick (predict.py:161-178).  For a *unidirectional* model the
recurrence makes that redundant: the hidden state after row ``t`` summarises
all history, so each tick only needs to feed the **newest row** and carry
the state — O(1) device work per tick instead of O(window), and tick
latency is one fused step (the north-star "jit state-carry" serving config,
BASELINE.json configs[4]).

The pooling head still wants max/mean pools over the last ``window`` steps,
so the carrier keeps a small ring of per-step hidden outputs (H-sized
vectors, not feature rows) and pools over it.

The flagship model is *bidirectional*; :class:`StreamingBiGRUBidirectional`
extends the same idea: the forward direction is carried exactly as above,
and the backward direction — which by definition needs the future of each
row, i.e. the window's newer rows — is re-scanned per tick over a small
ring of its *input projections* (3H-sized vectors).  Each tick is then one
fused jit step of O(window) work on H-sized state: no feature re-fetch, no
forward re-scan, no O(window x F) matmuls.

Semantics note: carried forward state sees the *entire* session history —
step ``t`` is bit-identical to scanning the whole stream from the start —
while the backward direction matches training exactly (h0 = 0 at the
newest row of the window).  The window-re-scan
:class:`~fmda_tpu.serve.predictor.Predictor` instead resets both
directions at the window edges (the training-time semantics,
sql_pytorch_dataloader windows).  Longer forward memory, O(1)/O(window)
ticks — choose per deployment; both are exposed, and both are verified
against explicit reference computations in tests.

The recurrent families stream through the same cores: ``cell="lstm"``
carries ``(h, c)`` instead of ``(h,)`` and re-scans the backward
direction with the LSTM recurrence; ``cell="ssm"`` (the O(1)-cache
family, fmda_tpu.ops.ssm) carries ``(s, ema_fast, ema_slow)`` — a
constant-size cache with **no ring at all**: its head pools with the
two carried EMAs instead of windowed max/mean, so the per-tick step is
matmul-free elementwise work and the exported session state is three
H-vectors instead of a ``(window, H)`` ring — dispatch via
:func:`_recurrent_cell_ops`.  The attn family deliberately has no
carried-state core: its sliding-window positions re-index every tick, so
the window re-encode IS the :class:`~fmda_tpu.serve.predictor.Predictor`.
"""

from __future__ import annotations

import logging
from typing import Callable, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from fmda_tpu.config import ModelConfig, TARGET_COLUMNS
from fmda_tpu.data.normalize import NormParams
from fmda_tpu.serve.predictor import labels_over_threshold
from fmda_tpu.ops.gru import GRUWeights, gru_gates, gru_scan
from fmda_tpu.ops.lstm import LSTMWeights, lstm_gates, lstm_scan
from fmda_tpu.ops.ssm import SSMWeights, select_ssm_step_fn, ssm_cell_step

log = logging.getLogger("fmda_tpu.serve")


def _layer_weights(params, reverse: bool, cell: str = "gru", layer: int = 0):
    suffix = f"l{layer}" + ("_reverse" if reverse else "")
    if cell == "ssm":
        return SSMWeights(
            params[f"weight_ih_{suffix}"], params[f"bias_ih_{suffix}"],
            params[f"a_base_{suffix}"], params[f"d_{suffix}"],
            params[f"rho_f_{suffix}"], params[f"rho_s_{suffix}"],
        )
    cls = GRUWeights if cell == "gru" else LSTMWeights
    return cls(
        params[f"weight_ih_{suffix}"], params[f"weight_hh_{suffix}"],
        params[f"bias_ih_{suffix}"], params[f"bias_hh_{suffix}"],
    )


def _layer0_weights(params, reverse: bool, cell: str = "gru"):
    return _layer_weights(params, reverse, cell, layer=0)


class CellOps(NamedTuple):
    """One recurrent family's carried-state serving contract.

    ``gate_step(xp, carry, w) -> (h_new, carry_new)`` advances one tick
    (carry is a tuple: ``(h,)`` for GRU, ``(h, c)`` for LSTM,
    ``(s, ema_fast, ema_slow)`` for SSM); ``bwd_scan(xp_nf, zeros, w)
    -> hs`` is the backward-direction window re-scan from a zero state
    (``None`` for families without one); ``head`` names the pooling
    state the core carries — ``"ring"`` (a (window, H) ring of per-step
    hiddens fed to :func:`pooled_head_logits`) or ``"carry"`` (the
    pooling state lives *inside* the cell carry and the head reads it
    via :func:`ema_head_logits`: no ring, nothing sized by ``window``).
    """

    gate_step: Callable
    bwd_scan: Optional[Callable]
    n_carry: int
    n_gates: int
    head: str


def _recurrent_cell_ops(cell: str, use_pallas: bool = False) -> CellOps:
    """:class:`CellOps` for a recurrent family.

    The attn family has no carried state — its window re-encode IS the
    :class:`~fmda_tpu.serve.predictor.Predictor` (sliding positions
    re-index every tick), so it deliberately stays out of this dispatch.

    ``use_pallas`` lets the SSM family request its fused serve-step
    kernel (per-shape selection at trace time, counted fallback
    elsewhere — :func:`fmda_tpu.ops.ssm.select_ssm_step_fn`); the
    GRU/LSTM per-tick step is a single small matmul + gate fusion XLA
    already compiles tightly, so they take no kernel here.
    """
    if cell == "gru":
        def gate_step(xp, carry, w):
            h_new = gru_gates(xp, carry[0], w.w_hh, w.b_hh)
            return h_new, (h_new,)

        def bwd_scan(xp_nf, zeros, w):
            return gru_scan(xp_nf, zeros, w.w_hh, w.b_hh)[1]

        return CellOps(gate_step, bwd_scan, 1, 3, "ring")
    if cell == "lstm":
        def gate_step(xp, carry, w):
            h_new, c_new = lstm_gates(xp, carry[0], carry[1], w.w_hh, w.b_hh)
            return h_new, (h_new, c_new)

        def bwd_scan(xp_nf, zeros, w):
            return lstm_scan(xp_nf, zeros, jnp.zeros_like(zeros),
                             w.w_hh, w.b_hh)[1]

        return CellOps(gate_step, bwd_scan, 2, 4, "ring")
    if cell == "ssm":
        def gate_step(xp, carry, w):
            # per-shape kernel-vs-jnp choice at trace time (shapes are
            # static under jit; the counted fallback fires at most once
            # per compiled program)
            step = select_ssm_step_fn(
                use_pallas,
                shape=(xp.shape[0], carry[0].shape[-1]),
                itemsize=xp.dtype.itemsize,
            ) if use_pallas else ssm_cell_step
            return step(xp, carry, w)

        # Numerical contract (docs/runtime.md "Numerical contract"): a
        # solo core and the pool are two compiled programs.  Same-program
        # comparisons — migration export/import, drain/replay, chaos
        # identity, every pool<->pool comparison — are bit-exact, for
        # every family.  Solo-vs-pool holds to a tolerance: 1 ulp apart
        # on XLA-CPU already (gru and ssm at bucket 1, jax 0.9.0), 1e-6
        # in the tests; on the TPU batched programs multiply at the
        # MXU's default precision and batch-1 programs do not, which
        # chip_smoke.py measures and bounds.
        return CellOps(gate_step, None, 3, 3, "carry")
    raise ValueError(
        "the carried-state streaming cores cover the recurrent families "
        "(cell='gru'/'lstm'/'ssm'); use the window-re-scan Predictor "
        f"for ModelConfig.cell={cell!r}"
    )


def advance_cells(params, cfg, gate_step, x, carries):
    """One tick through the stacked unidirectional cells: layer l's input
    at tick t is layer l-1's hidden output at tick t (no window
    dependence).  ``carries`` is a per-layer tuple of cell-carry tuples
    of (B, H) arrays; returns (last layer's h_new, new carries).

    Shared by the solo carrier and the fleet session pool
    (fmda_tpu/runtime/session_pool.py) so the per-tick math exists ONCE —
    the pool differs only in gathering/scattering its (B, H) slices from
    the pooled state tree.
    """
    layer_in = x
    new_carries = []
    h_new = None
    for layer in range(cfg.n_layers):
        w = _layer_weights(params, reverse=False, cell=cfg.cell,
                           layer=layer)
        xp = layer_in @ w.w_ih.T + w.b_ih
        h_new, carry_new = gate_step(xp, carries[layer], w)
        new_carries.append(carry_new)
        layer_in = h_new
    return h_new, tuple(new_carries)


def pooled_head_logits(params, h_last, ring, n_valid):
    """The trailing-window pooled head (biGRU_model.py:108-137 semantics)
    over a ring of per-step hidden outputs: masked max/mean pools of the
    valid window + last hidden, through the linear head.

    ``ring`` is (B, window, H); ``n_valid`` is a scalar (solo carrier,
    all lanes in lockstep) or (B, 1) (fleet pool, per-session tick
    counts) — the same broadcasting covers both, so the head exists once.
    """
    window = ring.shape[1]
    valid = (jnp.arange(window) < n_valid)[..., None]  # (W,1) or (B,W,1)
    neg = jnp.finfo(ring.dtype).min
    max_pool = jnp.max(jnp.where(valid, ring, neg), axis=1)
    avg_pool = jnp.sum(jnp.where(valid, ring, 0.0), axis=1) / n_valid
    concat = jnp.concatenate([h_last, max_pool, avg_pool], axis=-1)
    return concat @ params["linear"]["kernel"] + params["linear"]["bias"]


def ema_head_logits(params, h_last, carry_last):
    """The SSM family's head over its carried pooling state: concat
    ``[h_last, ema_fast, ema_slow]`` through the same ``linear`` params
    the train-mode twin (``models.common.ema_concat_logits``) creates —
    no ring, no window, O(1) state.  ``carry_last`` is the LAST layer's
    cell carry ``(s, ema_fast, ema_slow)``."""
    _, ema_fast, ema_slow = carry_last
    concat = jnp.concatenate([h_last, ema_fast, ema_slow], axis=-1)
    return concat @ params["linear"]["kernel"] + params["linear"]["bias"]


class StreamingBiGRU:
    """Carried-state streaming inference core for unidirectional models.

    Holds (h, ring of last ``window`` hidden outputs); each ``step(row)``
    advances the recurrence by one row and produces logits from the pooled
    head, exactly as a full re-scan of the trailing window would.

    ``cell="ssm"`` carries no ring at all (the pooling state is the two
    EMAs inside the cell carry; the ring buffer is kept zero-width so
    the step signature and donation layout stay uniform) — the carried
    state is a constant three H-vectors however large ``window`` is.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        norm: NormParams,
        *,
        window: int,
        batch: int = 1,
    ) -> None:
        ops = _recurrent_cell_ops(cfg.cell, use_pallas=cfg.use_pallas)
        gate_step, self._n_carry = ops.gate_step, ops.n_carry
        self._head = ops.head
        if cfg.bidirectional:
            raise ValueError(
                "carried-state streaming needs bidirectional=False; the "
                "backward direction would require the future. Use the "
                "window-re-scan Predictor for bidirectional models."
            )
        self.cfg = cfg
        self.window = window
        self.batch = batch
        self._dtype = jnp.dtype(cfg.dtype)
        dtype = self._dtype
        # compute dtype applied once here, not per tick (params are small
        # but the serving path is latency-critical)
        self._params = jax.tree.map(
            lambda a: jnp.asarray(a).astype(dtype), params)
        # norm stats are jit *arguments*, not closure constants: XLA
        # compiles a constant denominator differently from a traced one
        # (ulp-level), and the fleet runtime's session pool necessarily
        # passes per-slot norms as data — argument-passing here keeps a
        # solo carrier bit-identical to a multiplexed one
        # (tests/test_runtime.py), and lets live norm updates reuse the
        # compiled step.
        self._x_min = jnp.asarray(norm.x_min)
        self._x_range = jnp.asarray(norm.x_max - norm.x_min)

        def step(params, x_min, x_range, carry, ring, ring_pos, row):
            """One tick: row (B, F) -> (logits, new_carry, new_ring, pos).

            ``carry`` is a per-layer tuple of cell-carry tuples — stacked
            layers stay O(1)/tick (advance_cells; the ring pools the LAST
            layer's outputs, models/bigru.py:148-150).  Carry-head cells
            (ssm) skip the ring entirely and read their pooling state
            out of the last layer's carry."""
            x = ((row - x_min) / x_range).astype(dtype)
            h_new, carry_new = advance_cells(params, cfg, gate_step, x,
                                             carry)
            if self._head == "carry":
                logits = ema_head_logits(params, h_new, carry_new[-1])
                return logits, carry_new, ring, ring_pos + 1
            ring = jax.lax.dynamic_update_index_in_dim(
                ring, h_new, ring_pos % self.window, axis=1
            )
            n_valid = jnp.minimum(ring_pos + 1, self.window)
            logits = pooled_head_logits(params, h_new, ring, n_valid)
            return logits, carry_new, ring, ring_pos + 1

        # ring + pos donated: the per-tick state advances in place (the
        # ring is the core's big buffer — (B, window, H)).  The carry is
        # deliberately NOT donated: aliasing it changes XLA CPU's fusion
        # of the lstm gate math by one ulp, which would break the
        # solo-vs-multiplexed bit-identical contract the session pool
        # tests assert (the pool's own step donates its carry safely —
        # its gather/scatter program fuses differently).
        self._step = jax.jit(step, donate_argnums=(4, 5))
        self.reset()

    def reset(self) -> None:
        hidden = self.cfg.hidden_size
        # per-layer tuple of cell-carry tuples ((h,) GRU / (h, c) LSTM /
        # (s, ema_fast, ema_slow) SSM)
        self._h = tuple(
            tuple(jnp.zeros((self.batch, hidden), self._dtype)
                  for _ in range(self._n_carry))
            for _ in range(self.cfg.n_layers))
        # carry-head cells keep a zero-width ring: same step signature
        # and donation layout, no per-tick window state
        ring_w = self.window if self._head == "ring" else 0
        self._ring = jnp.zeros((self.batch, ring_w, hidden), self._dtype)
        self._pos = jnp.asarray(0, jnp.int32)

    @property
    def ticks_seen(self) -> int:
        return int(self._pos)

    def step(self, row: np.ndarray) -> np.ndarray:
        """Advance one tick with the newest feature row (B, F) or (F,);
        returns sigmoid probabilities (B, n_classes)."""
        row = jnp.asarray(row, jnp.float32)
        if row.ndim == 1:
            row = row[None, :]
        logits, self._h, self._ring, self._pos = self._step(
            self._params, self._x_min, self._x_range, self._h, self._ring,
            self._pos, row
        )
        return np.asarray(jax.nn.sigmoid(logits))


class StreamingBiGRUBidirectional:
    """Carried-state streaming inference for the flagship *bidirectional*
    model (north-star serving config: jit state-carry tick latency).

    Per tick, one fused jit step:

    - forward direction: advance the carried ``h_fwd`` by the newest row
      (O(1)), push the hidden output onto a ring;
    - backward direction: re-scan a ring of the window's backward input
      projections, newest→oldest, with ``h0 = 0`` at the newest row —
      training-exact backward semantics at O(window) cost on H-sized
      vectors (the features are projected once, on arrival);
    - pooled head (last-hidden sum + max/mean pools of the per-step
      direction sums, biGRU_model.py:108-137) over the valid window.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        norm: NormParams,
        *,
        window: int,
        batch: int = 1,
    ) -> None:
        ops = _recurrent_cell_ops(cfg.cell)
        if ops.head != "ring":
            # the bidirectional core's pooling sums per-step fwd+bwd
            # outputs over a ring — a carry-head family (ssm) has no
            # ring and serves unidirectionally (its whole point); the
            # window-re-scan Predictor covers its bidirectional models
            raise ValueError(
                f"cell={cfg.cell!r} has no bidirectional carried-state "
                "core; serve it with the unidirectional StreamingBiGRU "
                "(O(1) cache) or the window-re-scan Predictor")
        gate_step, bwd_scan = ops.gate_step, ops.bwd_scan
        self._n_carry, self._n_gates = ops.n_carry, ops.n_gates
        if not cfg.bidirectional:
            raise ValueError(
                "use StreamingBiGRU for unidirectional models (pure O(1))")
        if cfg.n_layers != 1:
            # stacked bidirectional streaming degenerates to a full window
            # re-encode (layer 1 needs layer 0's backward outputs over the
            # whole window, which change every tick) — that IS the
            # Predictor, so serve multi-layer bidirectional models there
            raise ValueError(
                "bidirectional carried-state streaming covers 1-layer "
                "models; use the window-re-scan Predictor for stacked "
                "bidirectional models")
        self.cfg = cfg
        self.window = window
        self.batch = batch
        self._dtype = jnp.dtype(cfg.dtype)
        dtype = self._dtype
        # compute dtype applied once here, not per tick (params are small
        # but the serving path is latency-critical)
        self._params = jax.tree.map(
            lambda a: jnp.asarray(a).astype(dtype), params)
        x_min = jnp.asarray(norm.x_min)
        x_range = jnp.asarray(norm.x_max - norm.x_min)
        w = window

        def step(params, carry, hs_ring, xpb_ring, pos, row):
            p = params
            wf = _layer0_weights(p, reverse=False, cell=cfg.cell)
            wb = _layer0_weights(p, reverse=True, cell=cfg.cell)
            x = ((row - x_min) / x_range).astype(dtype)

            # forward: one carried-gate step
            xpf = x @ wf.w_ih.T + wf.b_ih
            h_new, carry_new = gate_step(xpf, carry, wf)
            # project the row for the backward direction once, on arrival
            xpb = x @ wb.w_ih.T + wb.b_ih

            slot = pos % w
            hs_ring = jax.lax.dynamic_update_index_in_dim(
                hs_ring, h_new, slot, axis=1)
            xpb_ring = jax.lax.dynamic_update_index_in_dim(
                xpb_ring, xpb, slot, axis=1)

            # newest-first view of the ring: k-th entry is the k-th newest
            n_valid = jnp.minimum(pos + 1, w)
            idx = (pos - jnp.arange(w)) % w
            xpb_nf = jnp.take(xpb_ring, idx, axis=1)
            hs_fwd_nf = jnp.take(hs_ring, idx, axis=1)

            # backward direction: scan newest -> oldest with zero state at
            # the newest row (ticks past n_valid run on stale slots; their
            # outputs are masked out)
            h_bwd_seq = bwd_scan(xpb_nf, jnp.zeros_like(h_new), wb)
            h_bwd_last = jax.lax.dynamic_index_in_dim(
                h_bwd_seq, n_valid - 1, axis=1, keepdims=False)

            summed = hs_fwd_nf + h_bwd_seq
            valid = (jnp.arange(w) < n_valid)[None, :, None]
            neg = jnp.finfo(summed.dtype).min
            max_pool = jnp.max(jnp.where(valid, summed, neg), axis=1)
            avg_pool = jnp.sum(jnp.where(valid, summed, 0.0), axis=1) / n_valid
            last_hidden = h_new + h_bwd_last
            concat = jnp.concatenate([last_hidden, max_pool, avg_pool], axis=-1)
            logits = concat @ p["linear"]["kernel"] + p["linear"]["bias"]
            return logits, carry_new, hs_ring, xpb_ring, pos + 1

        # both rings + pos donated (in-place tick state advance; the
        # xpb ring is (B, window, n_gates*H) — the big buffer).  The
        # carry stays undonated for the same ulp-stability reason as
        # StreamingBiGRU's.
        self._step = jax.jit(step, donate_argnums=(2, 3, 4))
        self.reset()

    def reset(self) -> None:
        hidden = self.cfg.hidden_size
        # carry tuple: (h,) for GRU, (h, c) for LSTM
        self._h = tuple(
            jnp.zeros((self.batch, hidden), self._dtype)
            for _ in range(self._n_carry))
        self._hs_ring = jnp.zeros(
            (self.batch, self.window, hidden), self._dtype)
        self._xpb_ring = jnp.zeros(
            (self.batch, self.window, self._n_gates * hidden), self._dtype)
        self._pos = jnp.asarray(0, jnp.int32)

    @property
    def ticks_seen(self) -> int:
        return int(self._pos)

    def step(self, row: np.ndarray) -> np.ndarray:
        """Advance one tick with the newest feature row (B, F) or (F,);
        returns sigmoid probabilities (B, n_classes)."""
        row = jnp.asarray(row, jnp.float32)
        if row.ndim == 1:
            row = row[None, :]
        logits, self._h, self._hs_ring, self._xpb_ring, self._pos = self._step(
            self._params, self._h, self._hs_ring, self._xpb_ring, self._pos,
            row,
        )
        return np.asarray(jax.nn.sigmoid(logits))


class StreamingPredictor:
    """Bus-facing wrapper: consume predict-timestamp signals, feed only the
    newest landed row through the carried-state core, publish predictions."""

    #: catch-up fetch granularity: one query per this many missed rows
    #: (bounds both query count and peak memory of a long catch-up)
    CATCHUP_CHUNK = 10_000

    def __init__(
        self,
        bus,
        warehouse,
        core: "StreamingBiGRU | StreamingBiGRUBidirectional",
        *,
        threshold: float = 0.5,
        y_fields=TARGET_COLUMNS,
        signal_topic: str = "predict_timestamp",
        prediction_topic: str = "prediction",
        from_end: bool = True,
    ) -> None:
        self.bus = bus
        self.warehouse = warehouse
        self.core = core
        self.threshold = threshold
        self.y_fields = tuple(y_fields)
        self.prediction_topic = prediction_topic
        self._consumer = bus.consumer(signal_topic, from_end=from_end)
        self._last_row_id = 0

    def poll(self) -> List[Tuple[str, np.ndarray, Tuple[str, ...]]]:
        """Serve new signals; returns [(timestamp, probs, labels)].

        Rows are consumed strictly in id order; if signals skipped rows
        (e.g. predictor started mid-session), the gap rows are fed through
        the recurrence first so the carried state stays exact.  A signal
        carrying an in-band trace context gets a ``serve`` span recorded
        on it and the context propagated onto the prediction message.
        """
        from fmda_tpu.obs.trace import default_tracer, now_ns

        tracer = default_tracer()
        out = []
        for rec in self._consumer.poll():
            ts = rec.value.get("Timestamp")
            if not ts:
                continue
            trace = rec.value.get("trace")
            t0_ns = now_ns() if (trace is not None and tracer.enabled) else 0
            row_id = self.warehouse.id_for_timestamp(ts)
            if row_id is None or row_id <= self._last_row_id:
                continue
            # catch up any gap rows to keep the recurrence exact —
            # batched queries (a predictor started mid-session against a
            # long warehouse must not do thousands of single-row
            # round-trips), chunked so an arbitrarily long gap never
            # materialises as one unbounded matrix.  Positions are dense
            # (warehouse fetch space), so ranges are exactly the missed
            # rows, in order.
            for lo in range(self._last_row_id + 1, row_id + 1,
                            self.CATCHUP_CHUNK):
                hi = min(lo + self.CATCHUP_CHUNK - 1, row_id)
                for x in self.warehouse.fetch(range(lo, hi + 1)):
                    probs = self.core.step(x)[0]
            self._last_row_id = row_id
            idx, labels = labels_over_threshold(
                probs, self.threshold, self.y_fields)
            msg = {
                "timestamp": ts,
                "probabilities": [float(p) for p in probs],
                "prob_threshold": self.threshold,
                "pred_indices": list(idx),
                "pred_labels": list(labels),
            }
            if trace is not None:
                msg["trace"] = trace
            self.bus.publish(self.prediction_topic, msg)
            if t0_ns:
                tracer.add_span_wire(trace, "serve", "serve", t0_ns, now_ns())
            out.append((ts, probs, labels))
        return out
