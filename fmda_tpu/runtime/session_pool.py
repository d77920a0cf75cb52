"""Slot-pool session manager: N carried streaming states in one state tree.

The streaming carriers (:class:`fmda_tpu.serve.streaming.StreamingBiGRU`)
already accept a ``batch`` dimension, but a fixed batch serves tickers in
lockstep — every row advances every lane.  A serving fleet is the opposite
shape: thousands of independent sessions, each ticking on its own clock,
and any given micro-batch carries rows for an arbitrary *subset* of them.

:class:`SessionPool` packs up to ``capacity`` carried states into one
``(capacity+1, ...)`` state tree and exposes a single jitted step over a
*gather → batched cell → scatter* program:

- ``slots (B,)`` selects which sessions this flush advances; their carry,
  ring, and tick positions are gathered, advanced with exactly the solo
  carrier's ops (same normalize → input-proj → gate → ring-update →
  masked-pool → head sequence, so a multiplexed session is bit-identical
  to a solo run), and scattered back;
- the extra slot (index ``capacity``) is the **padding lane**: micro-batch
  lanes beyond the real request count point at it, so padded flushes need
  no active-lane mask inside the step — padding writes land in state no
  session reads ("dead slots don't pollute pooling" by construction);
- per-slot **generation counters** guard reuse: ``free`` bumps the slot's
  generation, so a :class:`SessionHandle` kept past ``free`` can never
  read or advance a recycled slot (the stale-session bug class of every
  slot-reuse cache; see the O(1)-cache serving papers in PAPERS.md).

The step is compiled once per distinct batch size ``B``; the micro-batcher
(:mod:`fmda_tpu.runtime.batcher`) quantises ``B`` to a few bucket sizes so
XLA compiles a handful of programs and replays them forever
(:attr:`SessionPool.compile_count` is the proof hook tests assert on).

Two serving-hot-path disciplines (ISSUE 3):

- **Donation** — the jitted step donates the carry/ring/pos buffers
  (``donate_argnums``), so XLA advances the pooled state *in place*
  instead of allocating and copying the whole (capacity+1, ...) tree on
  every flush.  The pool immediately rebinds its state attributes to the
  step's outputs, so no caller can observe the consumed buffers.
- **Async dispatch** — :meth:`step_device` returns the *device* array of
  probabilities without forcing the host transfer; the gateway overlaps
  flush k's transfer+publish with flush k+1's assembly+dispatch
  (:mod:`fmda_tpu.runtime.gateway`, the one-deep in-flight pipeline).
  :meth:`step` keeps the old blocking contract for direct callers.

**Sharding** — pass ``mesh`` to shard the *slot* axis of the state tree
across chips with :class:`~jax.sharding.NamedSharding` over the existing
(dp, sp) mesh (:mod:`fmda_tpu.parallel.mesh`): fleet capacity then scales
with device count (each chip holds ``n_slots / dp`` sessions' state; the
gather/scatter crosses chips only for the lanes that live elsewhere).
The slot count is padded up to a multiple of the dp axis so every shard
is equal-sized; the extra lanes are permanent padding nothing ever
allocates.  A ``mesh`` spanning **one** device (or ``mesh=None``) takes
the exact unsharded code path — bit-identical to the pre-sharding pool.

Scope: the unidirectional recurrent carriers (``cell="gru"``/``"lstm"``/
``"ssm"``, any ``n_layers`` — the pure O(1)-per-tick cores).
Bidirectional or attn serving re-encodes a window per tick; multiplex
those through the window-re-scan
:class:`~fmda_tpu.serve.predictor.Predictor` instead.

The ``cell="ssm"`` pool carries the family's **constant-size cache**:
three H-vectors per layer per session, a zero-width ring (the EMA head
needs no window state), and no per-tick matmul or gather beyond the
slot indexing — the smallest state tree of the families, which is what
donation, migration export (:meth:`export_slot`), and the columnar wire
blocks then move (docs/runtime.md "The SSM cell family").
"""

from __future__ import annotations

import logging
from dataclasses import dataclass
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from fmda_tpu.config import ModelConfig
from fmda_tpu.data.normalize import NormParams
from fmda_tpu.obs.device import tracked_jit
from fmda_tpu.serve.streaming import (
    _recurrent_cell_ops,
    advance_cells,
    ema_head_logits,
    pooled_head_logits,
)

log = logging.getLogger("fmda_tpu.runtime")


class PoolExhausted(Exception):
    """alloc() on a pool with no free slots (admission control reacts)."""


class StaleSessionError(Exception):
    """A SessionHandle used after its slot was freed (or re-allocated)."""


@dataclass(frozen=True)
class SessionHandle:
    """A claim on one pool slot, valid for exactly one generation."""

    session_id: str
    slot: int
    generation: int


class SessionPool:
    """Fixed-capacity pool of carried streaming states (one jitted step).

    ``alloc``/``free``/``reset`` manage slots host-side, off the hot
    path (each functional ``.at[slot].set`` update copies its
    (capacity+1, ...) array, so slot churn costs O(capacity) per call —
    fine at serving-session churn rates; a donate-based fused reset is
    the known optimisation if admission ever becomes hot).  ``step`` /
    ``step_device`` are the hot path — one fused jit call advancing every
    session named in ``slots`` by one tick, with the carry/ring/pos
    buffers donated so the state advances in place.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        capacity: int,
        window: int,
        mesh=None,
        shard_axis: str = "dp",
    ) -> None:
        cell_ops = _recurrent_cell_ops(cfg.cell, use_pallas=cfg.use_pallas)
        gate_step, self._n_carry = cell_ops.gate_step, cell_ops.n_carry
        self._head = cell_ops.head
        if cfg.bidirectional:
            raise ValueError(
                "SessionPool multiplexes the unidirectional carried-state "
                "cores (O(1)/tick); serve bidirectional models through the "
                "window-re-scan Predictor."
            )
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.cfg = cfg
        self.capacity = capacity
        self.window = window
        #: The padding lane every padded micro-batch points its unused
        #: lanes at — state no session is ever allocated.
        self.padding_slot = capacity
        self._dtype = jnp.dtype(cfg.dtype)
        dtype = self._dtype
        self._params = jax.tree.map(
            lambda a: jnp.asarray(a).astype(dtype), params)

        self.mesh = mesh
        self.n_shards = int(mesh.shape[shard_axis]) if mesh is not None else 1
        n_slots = capacity + 1
        if self.n_shards > 1:
            # pad the slot axis to a multiple of the shard count so every
            # chip holds an equal block; lanes past `capacity` are
            # permanent padding (never in the free list, never indexed)
            n_slots = -(-n_slots // self.n_shards) * self.n_shards
        #: Leading-axis length of every state leaf (>= capacity + 1).
        self.n_slots = n_slots
        if self.n_shards > 1:
            from fmda_tpu.parallel.mesh import (
                replicated_sharding,
                slot_sharding,
            )

            self._state_sharding = slot_sharding(mesh, shard_axis)
            self._repl_sharding = replicated_sharding(mesh)
            self._params = jax.tree.map(
                lambda a: jax.device_put(a, self._repl_sharding),
                self._params)

            def place(a):
                return jax.device_put(a, self._state_sharding)
        else:
            self._state_sharding = None
            self._repl_sharding = None

            def place(a):
                return a

        #: Re-pins a state leaf to the slot sharding after a host-side
        #: functional update (alloc/reset), so the jitted step's donation
        #: aliasing never sees a drifted layout.  Identity when unsharded.
        self._place_state = place

        hidden = cfg.hidden_size
        feats = cfg.n_features
        self._carry = tuple(
            tuple(place(jnp.zeros((n_slots, hidden), dtype))
                  for _ in range(self._n_carry))
            for _ in range(cfg.n_layers))
        # carry-head cells (ssm) keep a ZERO-WIDTH ring: the pooling
        # state lives inside the cell carry, so nothing in the pooled
        # tree is sized by `window` — donation, export_slot, and the
        # wire codec all carry the same (tiny) leaf unchanged
        ring_w = window if self._head == "ring" else 0
        self._ring = place(jnp.zeros((n_slots, ring_w, hidden), dtype))
        self._pos = place(jnp.zeros((n_slots,), jnp.int32))
        # per-slot normalization (sessions serve different tickers with
        # different price scales), gathered alongside the state
        self._x_min = place(jnp.zeros((n_slots, feats), jnp.float32))
        self._x_range = place(jnp.ones((n_slots, feats), jnp.float32))

        # host-side slot bookkeeping
        self._generations = [0] * capacity
        self._free: List[int] = list(range(capacity - 1, -1, -1))
        self._by_id: Dict[str, SessionHandle] = {}
        # fallback compile accounting for compile_count (distinct batch
        # sizes dispatched == programs compiled, since everything else
        # in the step signature is shape-stable)
        self._batch_sizes_seen: set = set()

        w = window

        def step(params, carry, ring, pos, x_min, x_range, slots, rows):
            """Advance the sessions in ``slots`` by one row each.

            Gather → the solo carrier's per-tick math
            (:func:`~fmda_tpu.serve.streaming.advance_cells` +
            :func:`~fmda_tpu.serve.streaming.pooled_head_logits`, shared
            code, not a copy) on a (B, ...) slice → scatter.  ``slots``
            must be duplicate-free over *live* slots (the batcher
            guarantees one row per session per flush); the padding lane
            may repeat freely — its scattered writes collide only with
            each other, in state nothing reads.
            """
            # The named scopes are metadata on the compiled operations
            # (docs/observability.md "Spans and scopes"): a profile's
            # device time falls under normalize / recurrence /
            # ring_update / head / state_writeback.
            with jax.named_scope("normalize"):
                x = ((rows - x_min[slots]) / x_range[slots]).astype(dtype)
            with jax.named_scope("recurrence"):
                pos_b = pos[slots]
                carry_b = tuple(
                    tuple(c[slots] for c in layer) for layer in carry)
                h_new, carry_new = advance_cells(params, cfg, gate_step, x,
                                                 carry_b)
            if self._head == "carry":
                # ssm: pooling state rides the carry; the zero-width
                # ring passes through untouched (kept for a uniform
                # step signature/donation layout)
                with jax.named_scope("head"):
                    logits = ema_head_logits(params, h_new, carry_new[-1])
            else:
                with jax.named_scope("ring_update"):
                    ring = ring.at[slots, pos_b % w].set(h_new)
                    ring_b = ring[slots]
                with jax.named_scope("head"):
                    # per-session valid trailing window: n_valid is
                    # (B, 1) here, a scalar in the solo carrier — same
                    # head either way
                    n_valid = jnp.minimum(pos_b + 1, w)[:, None]
                    logits = pooled_head_logits(
                        params, h_new, ring_b, n_valid)
            with jax.named_scope("state_writeback"):
                carry_out = tuple(
                    tuple(c.at[slots].set(cb)
                          for c, cb in zip(carry[layer], carry_new[layer]))
                    for layer in range(cfg.n_layers))
                pos = pos.at[slots].set(pos_b + 1)
            with jax.named_scope("head"):
                probs = jax.nn.sigmoid(logits)
            return probs, carry_out, ring, pos

        # carry/ring/pos are DONATED: the step advances the pooled state
        # in place (XLA aliases each donated input to its same-shape
        # output) instead of copying the whole (n_slots, ...) tree per
        # flush.  The attributes are rebound to the outputs immediately
        # below in step_device, so the consumed buffers are unreachable.
        donate = (1, 2, 3)
        # batch size (slots, arg 6) is the only varying shape in the
        # step signature — the cheap per-call program signature for the
        # compile ledger (fmda_tpu.obs.device)
        step_name = f"session_pool_step_{cfg.cell}"

        def sig(*a, **k):
            return ("B", int(a[6].shape[0]))

        if self.n_shards > 1:
            st, rp = self._state_sharding, self._repl_sharding
            # explicit shardings (pytree prefixes): state tree sharded on
            # the slot axis, params/norms-batch replicated — and the SAME
            # specs on the outputs, so donation aliasing holds shard for
            # shard.  slots/rows arrive replicated; XLA inserts the
            # cross-chip gather/scatter for foreign lanes.
            self._step = tracked_jit(
                step,
                name=step_name,
                signature_of=sig,
                donate_argnums=donate,
                in_shardings=(rp, st, st, st, st, st, rp, rp),
                out_shardings=(rp, st, st, st),
            )
        else:
            self._step = tracked_jit(
                step, name=step_name, signature_of=sig,
                donate_argnums=donate)

    # -- slot lifecycle (host-side, off the hot path) -----------------------

    def alloc(
        self, session_id: str, norm: Optional[NormParams] = None
    ) -> SessionHandle:
        """Claim a free slot for ``session_id``: zeroed state, the
        session's own normalization stats, a fresh generation."""
        if session_id in self._by_id:
            raise ValueError(f"session {session_id!r} already allocated")
        if not self._free:
            raise PoolExhausted(
                f"all {self.capacity} slots in use ({len(self._by_id)} "
                "sessions); free one or raise RuntimeConfig.capacity")
        slot = self._free.pop()
        self._reset_slot(slot)
        if norm is not None:
            x_min = np.asarray(norm.x_min, np.float32)
            x_range = np.asarray(norm.x_max, np.float32) - x_min
            self._x_min = self._place_state(self._x_min.at[slot].set(x_min))
            self._x_range = self._place_state(
                self._x_range.at[slot].set(x_range))
        else:
            self._x_min = self._place_state(self._x_min.at[slot].set(0.0))
            self._x_range = self._place_state(
                self._x_range.at[slot].set(1.0))
        handle = SessionHandle(session_id, slot, self._generations[slot])
        self._by_id[session_id] = handle
        return handle

    def free(self, handle: SessionHandle) -> None:
        """Release the slot.  The generation bump invalidates every copy
        of ``handle`` — a later ``step``/``check`` with it raises instead
        of touching whichever session re-used the slot."""
        self.check(handle)
        self._generations[handle.slot] += 1
        del self._by_id[handle.session_id]
        self._free.append(handle.slot)

    def reset(self, handle: SessionHandle) -> None:
        """Zero the session's carried state in place (same slot, same
        generation — for a client restarting its stream)."""
        self.check(handle)
        self._reset_slot(handle.slot)

    def _reset_slot(self, slot: int) -> None:
        place = self._place_state
        self._carry = tuple(
            tuple(place(c.at[slot].set(0.0)) for c in layer)
            for layer in self._carry)
        self._ring = place(self._ring.at[slot].set(0.0))
        self._pos = place(self._pos.at[slot].set(0))

    def export_slot(self, handle: SessionHandle) -> dict:
        """Snapshot one session's carried state as host numpy arrays —
        the migration payload (fmda_tpu.fleet): carry per layer, ring,
        tick position, and the per-slot normalization stats.  Raw-dtype
        copies, so an :meth:`import_slot` on another pool (same model
        config) reproduces the slot bit for bit."""
        self.check(handle)
        s = handle.slot
        return {
            "carry": [
                [np.asarray(c[s]) for c in layer] for layer in self._carry
            ],
            "ring": np.asarray(self._ring[s]),
            "pos": int(self._pos[s]),
            "x_min": np.asarray(self._x_min[s]),
            "x_range": np.asarray(self._x_range[s]),
        }

    def import_slot(self, handle: SessionHandle, state: dict) -> None:
        """Load an :meth:`export_slot` snapshot into this slot (the
        receiving end of a migration).  Functional ``.at[slot].set``
        writes of same-dtype arrays — bit-exact, same cost class as
        ``alloc``/``reset`` (host-side, off the hot path)."""
        self.check(handle)
        s = handle.slot
        if len(state["carry"]) != self.cfg.n_layers:
            raise ValueError(
                f"state has {len(state['carry'])} carry layers, pool "
                f"expects {self.cfg.n_layers} (model config mismatch?)")
        place = self._place_state
        self._carry = tuple(
            tuple(
                place(c.at[s].set(jnp.asarray(arr, c.dtype)))
                for c, arr in zip(layer, state_layer)
            )
            for layer, state_layer in zip(self._carry, state["carry"])
        )
        self._ring = place(
            self._ring.at[s].set(jnp.asarray(state["ring"],
                                             self._ring.dtype)))
        self._pos = place(self._pos.at[s].set(int(state["pos"])))
        self._x_min = place(
            self._x_min.at[s].set(jnp.asarray(state["x_min"], jnp.float32)))
        self._x_range = place(
            self._x_range.at[s].set(
                jnp.asarray(state["x_range"], jnp.float32)))

    def is_live(self, handle: SessionHandle) -> bool:
        return (
            0 <= handle.slot < self.capacity
            and self._generations[handle.slot] == handle.generation
            and self._by_id.get(handle.session_id) == handle
        )

    def check(self, handle: SessionHandle) -> None:
        if not self.is_live(handle):
            reallocated = any(
                h.slot == handle.slot for h in self._by_id.values())
            raise StaleSessionError(
                f"handle for session {handle.session_id!r} (slot "
                f"{handle.slot}, generation {handle.generation}) is no "
                "longer live — the slot was freed"
                + (" and re-allocated to another session"
                   if reallocated else ""))

    def handle_for(self, session_id: str) -> Optional[SessionHandle]:
        return self._by_id.get(session_id)

    def session_ids(self) -> List[str]:
        """Ids of every live session (the worker's session report —
        router failover rebuilds its registry from these)."""
        return list(self._by_id)

    def slot_norm(self, handle: SessionHandle) -> tuple:
        """One session's normalization stats as host ``(x_min, x_range)``
        arrays — the cheap slice a session report carries (the full
        :meth:`export_slot` hauls the ring too)."""
        self.check(handle)
        s = handle.slot
        return np.asarray(self._x_min[s]), np.asarray(self._x_range[s])

    def ticks_seen(self, handle: SessionHandle) -> int:
        self.check(handle)
        return int(self._pos[handle.slot])

    @property
    def n_active(self) -> int:
        return len(self._by_id)

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def active_mask(self) -> np.ndarray:
        """(capacity,) bool — which slots currently carry a live session."""
        mask = np.zeros(self.capacity, bool)
        for h in self._by_id.values():
            mask[h.slot] = True
        return mask

    @property
    def compile_count(self) -> int:
        """Distinct compiled programs behind the jitted step — one per
        micro-batch bucket size.  Tests assert this stays equal to the
        number of buckets actually dispatched (no per-request recompiles).

        Probes jax's jit cache directly when the (private) hook exists —
        the honest measurement; falls back to counting distinct dispatched
        batch sizes (equivalent here: batch size is the only varying
        shape in the step signature) if a jax upgrade removes it.
        """
        size = self._step.cache_size()
        if size is not None:
            return size
        return len(self._batch_sizes_seen)

    def mark_warm(self) -> None:
        """Declare precompile over: any further compile of the step is
        an *unexpected recompile* — counted by the compile ledger,
        evented, and SLO-alertable (fmda_tpu.obs.device)."""
        self._step.mark_warm()

    @property
    def recompiles_after_warmup(self) -> int:
        """Compiles observed after :meth:`mark_warm` (0 is the
        steady-state contract the chaos/elastic soaks hard-gate)."""
        return self._step.unexpected_recompiles

    def live_tree(self):
        """The pool's live device tree (params + pooled state + norms)
        — the owner callback for the device memory monitor."""
        return (self._params, self._carry, self._ring, self._pos,
                self._x_min, self._x_range)

    def swap_weights(self, params) -> None:
        """Land a new checkpoint into the live pool without touching a
        single session.

        ``params`` is the first argument of the jitted step and is *not*
        donated, so the swap is a pure host-side rebind: cast the new
        tree to the pool dtype, re-place it on the replicated sharding
        when the pool is sharded, and point ``self._params`` at it.  The
        next flush serves the new weights; carried state, rings, norms,
        and slot bookkeeping are untouched, and because the tree
        structure and every leaf shape are validated against the serving
        tree the jit cache hits — zero recompiles, zero dropped
        sessions.  Structure or shape drift raises ``ValueError`` (a
        silent recompile storm is worse than a refused swap).
        """
        dtype = self._dtype
        old_leaves, old_treedef = jax.tree.flatten(self._params)
        raw_leaves, new_treedef = jax.tree.flatten(params)
        # structure first, cast second: a malformed checkpoint must be
        # refused as ValueError before any leaf touches the dtype lattice
        if new_treedef != old_treedef:
            raise ValueError(
                "swap_weights: checkpoint tree structure differs from the "
                f"serving tree ({new_treedef} vs {old_treedef})")
        new_leaves = [jnp.asarray(a).astype(dtype) for a in raw_leaves]
        new = jax.tree.unflatten(new_treedef, new_leaves)
        for old, fresh in zip(old_leaves, new_leaves):
            if old.shape != fresh.shape:
                raise ValueError(
                    "swap_weights: leaf shape mismatch "
                    f"{fresh.shape} vs serving {old.shape} — a hot swap "
                    "must not change the compiled program")
        if self._repl_sharding is not None:
            new = jax.tree.map(
                lambda a: jax.device_put(a, self._repl_sharding), new)
        self._params = new

    # -- the hot path -------------------------------------------------------

    def step_device(self, slots: np.ndarray, rows: np.ndarray):
        """One fused flush, asynchronously: advance ``slots[i]`` by
        ``rows[i]`` and return the (B, n_classes) sigmoid probabilities
        as a **device array** — no host transfer, no block.  The pool's
        state advances in place (donated buffers) the moment the step is
        enqueued; the caller forces the result whenever it actually needs
        the numbers (the gateway does so one flush late, overlapping the
        transfer with the next flush's dispatch).

        ``slots`` (B,) int32 — pool slots, padded lanes = ``padding_slot``;
        ``rows`` (B, F) float32.  Padding lanes carry garbage; callers
        slice them off.  Caller contract: at most one lane per live slot,
        handles already validated (the gateway/batcher do both).
        """
        slots = np.asarray(slots, np.int32)
        rows = np.asarray(rows, np.float32)
        self._batch_sizes_seen.add(int(slots.shape[0]))
        probs, self._carry, self._ring, self._pos = self._step(
            self._params, self._carry, self._ring, self._pos,
            self._x_min, self._x_range, slots, rows,
        )
        return probs

    def step(self, slots: np.ndarray, rows: np.ndarray) -> np.ndarray:
        """Blocking :meth:`step_device`: one fused flush, probabilities
        as a host numpy array (the pre-pipeline contract, kept for direct
        callers and tests)."""
        return np.asarray(self.step_device(slots, rows))
