"""Training harness: jitted steps, chunked epochs, checkpointing.

Replaces the reference's notebook training loop
(biGRU_model_training.ipynb cells 11-39 + biGRU_model.py:162-286) with a
proper API.  Same semantics — chunk-level contiguous split, per-chunk
normalization, weighted BCE, Adam with global-norm clip 50, per-batch
metrics averaged per epoch — but everything device-side, and with what
is particular to a family (its batches, loss and per-step values) asked
of the family's task (:mod:`fmda_tpu.train.tasks`), so that a
next-token decoder trains through the same loop:

- one compiled ``train_step``/``eval_step`` reused for every batch (fixed
  shapes via padded+masked batches — no per-batch Python/sklearn work);
- where a step is small beside what a call into it costs the host, one
  call carries a *group* of consecutive steps (:func:`group_size`);
- gradients, clipping, Adam, and all four metrics fused into the step;
- optional data parallelism: pass a :class:`jax.sharding.Mesh` and the step
  shards the batch across the ``dp`` axis (XLA inserts the ICI all-reduce
  for gradients automatically).
"""

from __future__ import annotations

import itertools
import logging
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np
import optax

from fmda_tpu.config import ModelConfig, TrainConfig
from fmda_tpu.data.pipeline import (
    Batch, BatchGroup, ChunkDataset, background_compose, group_batches,
    prefetch_batches)
from fmda_tpu.data.source import FeatureSource
from fmda_tpu.models import build_model
from fmda_tpu.obs.device import tracked_jit
from fmda_tpu.train.losses import class_weights
from fmda_tpu.train.tasks import EpochMetrics, StepTotals, task_for

log = logging.getLogger("fmda_tpu.train")

#: The most steps one call into a compiled step carries.
MAX_GROUP_STEPS = 16
#: The most bytes a group of batches may hold on the device: a pass keeps
#: ``train.prefetch_depth`` of them ahead of the loop and pads its last
#: one to the full group.
GROUP_BYTES_CAP = 64 << 20
#: A step that must move this many bytes (its state and one batch, each
#: once) runs alone.  A call into a compiled step costs the host 0.15 ms
#: (a bare host) to 0.55 ms (the chip machine's, PERF.md section 6), and
#: reading 256 MiB of state and writing it back is 537 MB: 0.66 ms at a
#: v5e's 819 GB/s.  From here on the device takes longer over a step
#: than the host over the call, and a group would buy nothing.
SOLO_STEP_BYTES = 256 << 20


def group_size(state_bytes: int, batch_bytes: int) -> int:
    """How many consecutive steps one call into the compiled step
    carries, from the bytes a step must move at the least: 1 where the
    step cannot be host-bound (``SOLO_STEP_BYTES``), else as many
    batches as ``GROUP_BYTES_CAP`` holds, ``MAX_GROUP_STEPS`` at most.

    The two regimes met so far lie orders of magnitude apart (a width-32
    recurrent classifier: 0.6 MB of state, 3.4 MB a batch, 16; the
    decoder: 7.88 GB of state, 1), so the constants decide nothing yet
    and are no setting: a user who could tune them could only make a
    pass slower or its tail group larger."""
    if state_bytes + batch_bytes >= SOLO_STEP_BYTES:
        return 1
    return max(1, min(MAX_GROUP_STEPS,
                      GROUP_BYTES_CAP // max(batch_bytes, 1)))


def _tree_bytes(tree) -> int:
    return sum(a.nbytes for a in jax.tree.leaves(tree))


@flax.struct.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jax.Array


def _batch_of(group: Batch, i) -> Batch:
    """Batch ``i`` of a group (leaves ``(k, 1, ...)``:
    :func:`~fmda_tpu.data.pipeline.group_batches`), inside a compiled
    step."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)[0],
        group)


def _add_step(totals, values):
    """``totals + this step's values``, inside a compiled step (under its
    ``metrics`` scope)."""
    return jax.tree.map(jnp.add, totals, values)


class Trainer:
    """Builds the model + optimizer and runs chunked epochs over a source."""

    def __init__(
        self,
        model_cfg: ModelConfig,
        train_cfg: TrainConfig,
        *,
        weight: Optional[np.ndarray] = None,
        pos_weight: Optional[np.ndarray] = None,
        mesh: Optional[jax.sharding.Mesh] = None,
        dp_axis: str = "dp",
    ) -> None:
        self.model_cfg = model_cfg
        self.train_cfg = train_cfg
        self.model = build_model(model_cfg)
        self.optimizer = optax.chain(
            optax.clip_by_global_norm(train_cfg.clip),
            optax.adam(train_cfg.learning_rate),
        )
        self.weight = None if weight is None else jnp.asarray(weight)
        self.pos_weight = None if pos_weight is None else jnp.asarray(pos_weight)
        self.task = task_for(model_cfg, train_cfg, weight=self.weight,
                             pos_weight=self.pos_weight)
        self.mesh = mesh
        self.dp_axis = dp_axis
        # each step kind as two programs of one body: the step alone
        # (single_step, and the loop's where group_size says 1) and the
        # step run over a group of batches in one call
        self._train_step, self._train_group = self._build_train_step()
        self._eval_step, self._eval_group = self._build_eval_step()
        # placed-batch cache: (id(dataset), chunk tuple) -> (dataset,
        # [BatchGroup], or [Batch] where steps run alone) — see
        # _run_chunks; the dataset ref pins id() validity
        self._placed_cache: Dict[Any, Tuple[Any, List[Any]]] = {}
        # epochs fit / fit_multi have finished: the index of the one
        # being run, on its record and on its pass-level spans
        self._epochs_run = 0

    # -- state ---------------------------------------------------------------

    def _init_state_local(self, rng: jax.Array) -> TrainState:
        """Fresh state on the default device (no mesh placement)."""
        params = self.task.init_params(self.model, rng)
        opt_state = self.optimizer.init(params)
        return TrainState(
            params=params,
            opt_state=opt_state,
            step=jnp.zeros((), jnp.int32),
        )

    def _place_state(self, state: TrainState) -> TrainState:
        if self.mesh is not None:
            # multi-process safe: plain device_put onto a sharding that
            # spans processes runs a host-side cross-process assert some
            # CPU builds cannot execute (parallel/distributed.py)
            from fmda_tpu.parallel.distributed import place_replicated

            state = place_replicated(self.mesh, state)
        return state

    def init_state(self, rng: jax.Array) -> TrainState:
        return self._place_state(self._init_state_local(rng))

    def restore_state(self, checkpoint_path: str) -> TrainState:
        """Exact-resume a checkpoint into this trainer's state structure.

        The raw checkpoint tree stores the optimizer state as plain
        containers; its leaves are grafted back onto the typed optax
        structure a fresh ``init_state`` provides, so ``fit(...,
        initial_state=restore_state(p))`` continues training bit-exactly
        (step counter included — the dropout stream folds on it).
        """
        from fmda_tpu.train.checkpoint import restore_checkpoint

        tree, norm = restore_checkpoint(checkpoint_path)
        # remembered so a subsequent fit() can detect that the data source
        # (and hence the recomputed normalization) changed since the save
        self._restored_norm = norm
        # structure/dtype template only: shapes, never arrays (a second
        # state beside the restored one is 8 GB at the decoder's widths)
        template = jax.eval_shape(
            self._init_state_local, jax.random.PRNGKey(0))
        params = jax.tree.map(
            lambda t, r: jnp.asarray(r, t.dtype), template.params,
            tree["params"],
        )
        opt_state = jax.tree.unflatten(
            jax.tree.structure(template.opt_state),
            [jnp.asarray(leaf) for leaf in jax.tree.leaves(tree["opt_state"])],
        )
        return self._place_state(TrainState(
            params=params, opt_state=opt_state,
            step=jnp.asarray(int(tree["step"]), jnp.int32),
        ))

    # -- compiled steps ------------------------------------------------------

    def _batch_sharding(self):
        if self.mesh is None:
            return None
        from fmda_tpu.parallel.mesh import batch_sharding

        return batch_sharding(self.mesh, self.dp_axis)

    def _group_sharding(self):
        """A group's batches keep their dp split, now along axis 2
        (behind the step axis and the unit axis)."""
        if self.mesh is None:
            return None
        return jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec(None, None, self.dp_axis))

    def _jit_step(self, fn, name: str, *, grouped: bool):
        """A step function as a tracked jit that donates what it returns
        anew (the train step's state and totals, the eval step's
        totals).

        With a mesh the compiled steps carry explicit in/out shardings:
        params/optimizer state replicated over every device, the batch
        split along the dp axis (XLA inserts the gradient all-reduce).
        A 1-device mesh lowers to the identical program as the meshless
        jit — bit-identity is test-pinned (tests/test_train_parallel.py).
        """
        train = name == "train_step"
        jit_kwargs: Dict[str, Any] = {
            "donate_argnums": (0, 1) if train else (1,)}
        if self.mesh is not None:
            from fmda_tpu.parallel.mesh import replicated_sharding

            replicated = replicated_sharding(self.mesh)
            batched = (self._group_sharding() if grouped
                       else self._batch_sharding())
            # after the batch: a group's live count, a train step's key
            rest = (replicated,) * (int(grouped) + int(train))
            jit_kwargs["in_shardings"] = (
                replicated, replicated, Batch(batched, batched, batched),
                *rest)
            jit_kwargs["out_shardings"] = (
                (replicated, replicated) if train else replicated)
        return tracked_jit(fn, name=name, **jit_kwargs)

    def _build_train_step(self):
        model, tc, task = self.model, self.train_cfg, self.task
        accum = tc.accum_steps
        fold = getattr(task, "fold", _add_step)

        def grads_full(params, batch: Batch, dropout_rng):
            def loss_fn(params):
                with jax.named_scope("forward"):
                    out = task.forward(model, params, batch, dropout_rng)
                with jax.named_scope("loss"):
                    return task.loss(params, out, batch)

            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params
            )
            return loss, aux, grads

        def grads_accum(params, batch: Batch, dropout_rng):
            # (B, ...) -> (K, B/K, ...): equal fixed-shape microbatches
            # scanned into summed gradients.  The masked loss is a global
            # mean (sum / valid-element count), so the scan accumulates
            # the *unnormalized* loss sum, gradient-of-sum, and element
            # count, and normalizes once at the end — the full-batch
            # gradient exactly, up to float re-association
            # (docs/training.md "Accumulation math").  Peak activation
            # memory is one microbatch instead of the full batch.
            micro = jax.tree.map(
                lambda a: a.reshape((accum, a.shape[0] // accum)
                                    + a.shape[1:]),
                batch,
            )

            def sum_loss_fn(params, mb: Batch, mb_rng):
                with jax.named_scope("forward"):
                    out = task.forward(model, params, mb, mb_rng)
                with jax.named_scope("loss"):
                    s, count, aux = task.loss_sums(params, out, mb)
                return s, (count, aux)

            def body(carry, xs):
                grad_sum, loss_sum, count_sum = carry
                mb, k = xs
                # each microbatch gets its own dropout stream (folded on
                # the microbatch index) — full/accumulated equivalence is
                # stated at dropout 0.0
                (s, (count, aux)), g = jax.value_and_grad(
                    sum_loss_fn, has_aux=True
                )(params, mb, jax.random.fold_in(dropout_rng, k))
                carry = (
                    jax.tree.map(jnp.add, grad_sum, g),
                    loss_sum + s,
                    count_sum + count,
                )
                return carry, aux

            zeros = jax.tree.map(jnp.zeros_like, params)
            init = (zeros, jnp.zeros((), jnp.float32),
                    jnp.zeros((), jnp.float32))
            (grad_sum, loss_sum, count_sum), aux_k = jax.lax.scan(
                body, init, (micro, jnp.arange(accum))
            )
            denom = jnp.maximum(count_sum, 1.0)
            grads = jax.tree.map(lambda g: g / denom, grad_sum)
            # metrics run on the full batch's values (a classifier's
            # logits, concatenated), same as the K=1 path
            return loss_sum / denom, task.merge_micro(aux_k), grads

        def step_fn(state: TrainState, totals, batch: Batch,
                    rng: jax.Array):
            with jax.named_scope("forward"):  # the forward's dropout key
                dropout_rng = jax.random.fold_in(rng, state.step)
            if accum == 1:
                loss, aux, grads = grads_full(
                    state.params, batch, dropout_rng)
            else:
                loss, aux, grads = grads_accum(
                    state.params, batch, dropout_rng)
            # named scopes are metadata on the compiled operations (the
            # profile's device lines read them; docs/observability.md
            # "Spans and scopes"): forward / loss inside the gradient,
            # whose backward half JAX names transpose(jvp(forward))
            with jax.named_scope("optimizer"):
                updates, opt_state = self.optimizer.update(
                    grads, state.opt_state, state.params
                )
                params = optax.apply_updates(state.params, updates)
                if hasattr(task, "after_update"):
                    params = task.after_update(params, aux)
            with jax.named_scope("metrics"):
                totals = fold(totals, task.step_values(loss, aux, batch))
            new_state = TrainState(
                params=params, opt_state=opt_state, step=state.step + 1
            )
            return new_state, totals

        def group_fn(state: TrainState, totals, group: Batch, n_live,
                     rng: jax.Array):
            # the first n_live batches of the group, one step each, in
            # order: state and totals are the carry, so the dropout key
            # folds on the step counter as it does over single calls and
            # the padding behind n_live is never read.  Nothing
            # differentiates through the loop; the gradient is inside.
            def body(i, carry):
                return step_fn(*carry, _batch_of(group, i), rng)

            return jax.lax.fori_loop(0, n_live, body, (state, totals))

        return (
            self._jit_step(step_fn, "train_step", grouped=False),
            self._jit_step(group_fn, "train_step", grouped=True))

    def _build_eval_step(self):
        model, task = self.model, self.task
        fold = getattr(task, "fold", _add_step)
        # the training objective less the terms an evaluation leaves out
        eval_loss = getattr(task, "eval_loss", task.loss)

        def eval_fn(params, totals, batch: Batch):
            with jax.named_scope("forward"):
                out = task.forward(model, params, batch, None)
            with jax.named_scope("loss"):
                loss, aux = eval_loss(params, out, batch)
            with jax.named_scope("metrics"):
                return fold(totals, task.step_values(loss, aux, batch))

        def group_fn(params, totals, group: Batch, n_live):
            def body(i, totals):
                return eval_fn(params, totals, _batch_of(group, i))

            return jax.lax.fori_loop(0, n_live, body, totals)

        return (
            self._jit_step(eval_fn, "eval_step", grouped=False),
            self._jit_step(group_fn, "eval_step", grouped=True))

    def zero_totals(self):
        """A pass's accumulators at zero (the task's: :class:`StepTotals`
        for a classifier), placed as the compiled steps return them (same
        dtypes, strong types and placement, so a pass's first step runs
        the executable its second does)."""
        totals = self.task.zero_totals()
        if self.mesh is None:
            return jax.device_put(totals)  # one placement for all leaves
        return self._place_state(totals)

    def single_step(
        self, state: TrainState, batch: Batch,
        rng: Optional[jax.Array] = None,
    ) -> Tuple[TrainState, Any]:
        """One step's own loss and metrics, through the program the step
        loop runs: a train step with ``rng`` (``state``'s buffers are
        donated, as in the loop), an eval step without."""
        if rng is None:
            return state, self._eval_step(
                state.params, self.zero_totals(), batch)
        return self._train_step(state, self.zero_totals(), batch, rng)

    # -- compile accounting ---------------------------------------------------

    def _steps(self):
        return (self._train_step, self._train_group,
                self._eval_step, self._eval_group)

    def mark_warm(self) -> None:
        """Declare step warm-up over: any compile after this is counted
        as *unexpected* on the compile ledger (the contract
        tests/test_step_totals.py and the continuous loop's tests pin).

        It is also where the warm programs are asked what they hold
        (:meth:`step_memory`): the answer goes into the ledger's compile
        records, which outlive this trainer, and whoever reads them after
        the owner is gone (the benchmark's readers do) has no function
        left to ask.  Two cached lowerings, about a millisecond; nothing
        is compiled (tests/test_epoch_record.py)."""
        for step in self._steps():
            step.mark_warm()
        self.step_memory()

    def step_memory(self) -> Dict[str, Optional[Dict[str, object]]]:
        """What the compiled steps reserve on the device, by the
        compiler's own analysis of the programs ``fit`` ran — of each
        kind the single or the grouped one, whichever has the calls:
        ``{"train_step": ..., "eval_step": ...}``, each
        :meth:`fmda_tpu.obs.device.TrackedFunction.memory`'s answer
        (None for a kind that has not compiled, or on a backend without
        the analysis).  Asked once a program and memoised; never a
        compile."""
        def ran(single, group):
            return (group if group.calls > single.calls else single).memory()

        return {"train_step": ran(self._train_step, self._train_group),
                "eval_step": ran(self._eval_step, self._eval_group)}

    @property
    def unexpected_recompiles(self) -> int:
        return sum(step.unexpected_recompiles for step in self._steps())

    @property
    def compile_counts(self) -> Dict[str, Optional[int]]:
        """Distinct compiled programs per step kind — the pin
        tests/test_step_totals.py asserts (batches are always padded to
        ``batch_size`` and a pass's last group to the full group, so a
        ``fit`` compiles each kind exactly once: the grouped program or
        the single one, by :func:`group_size`; ``single_step`` beside a
        grouped loop is a second).  None when jax's (private) cache
        probe is unavailable."""
        def both(single, group):
            counts = (single.cache_size(), group.cache_size())
            return None if None in counts else sum(counts)

        return {"train_step": both(self._train_step, self._train_group),
                "eval_step": both(self._eval_step, self._eval_group)}

    # -- batch plumbing ------------------------------------------------------

    def _place_batches(self, batches: Iterable[Batch],
                       state: Optional[TrainState] = None) -> Iterable:
        """The overlapped input pipeline: host composition runs in a
        background thread, composed batches are transferred immediately
        (dp batch sharding under a mesh; when the job spans processes
        each process's batches are its *local* shard of the global batch
        and are assembled in place), and up to ``train.prefetch_depth``
        placed batches ride ahead of the step loop.  What the step loop
        waits for it is measured where the loop pulls
        (``_run_batches``: ``train_input_stall_seconds``).

        With the ``state`` a pass steps, the batches are grouped as the
        step loop will run them: :func:`group_size` of the state's bytes
        and the first batch's says how many consecutive host batches are
        stacked and placed as one :class:`BatchGroup`, three transfers a
        group; where it says 1, and without a state (what
        ``single_step`` takes), each batch is placed alone.  Stacking
        runs on the composer thread and the composition behind it on a
        thread of its own: a stacked group is 54 MB of fresh host
        pages, as dear as the window gather that fills it (PERF.md
        section 6, PR 29)."""
        depth = self.train_cfg.prefetch_depth
        k = 1
        if state is not None:
            batches = iter(batches)
            first = next(batches, None)
            if first is None:
                return iter(())
            k = group_size(_tree_bytes(state), _tree_bytes(first))
            batches = itertools.chain((first,), batches)
        if k == 1:
            return prefetch_batches(
                batches, self._placer(self._batch_sharding()), depth=depth)
        if depth > 0:
            batches = background_compose(batches, depth=depth * k)
        place = self._placer(self._group_sharding())
        return prefetch_batches(
            group_batches(batches, k),
            lambda group: BatchGroup(place(group.batches), group.n_live),
            depth=depth)

    def _placer(self, sharding):
        """``Batch -> placed Batch`` for a batch (or a group's stacked
        batches) split over dp as ``sharding`` says."""
        if sharding is None:
            return jax.device_put  # one call for the three leaves
        if jax.process_count() > 1:
            from fmda_tpu.parallel.distributed import place_local_batch

            return lambda b: place_local_batch(self.mesh, b, sharding.spec)
        return lambda b: Batch(*(jax.device_put(a, sharding) for a in b))

    def _chunk_batches(self, dataset, chunk_idx: int) -> Iterable[Batch]:
        """One chunk's batches, each placed alone: what ``single_step``
        and the single programs take."""
        return self._place_batches(self.task.batches(dataset, chunk_idx))

    # -- epochs --------------------------------------------------------------

    def _run_chunks(
        self,
        state: TrainState,
        dataset: ChunkDataset,
        chunk_indices: Sequence[int],
        rng: Optional[jax.Array],
        train: bool,
        account: Optional[Dict[str, Any]] = None,
    ) -> Tuple[TrainState, EpochMetrics, np.ndarray]:
        # one flat host generator over every chunk, behind one pipeline:
        # the window gather/normalization of chunk k+1 (cached after the
        # first epoch — ChunkDataset.windows) happens in the composer
        # thread while the device computes on chunk k's batches.
        #
        # With ``cache_chunks`` set, the PLACED batches of the first
        # pass (groups of them, as they were placed) are kept and later
        # epochs replay the device-side buffers directly — no re-gather,
        # no re-pad, no re-transfer (batches are never donated, so reuse
        # is safe; same arrays -> bit-identical epochs).  RAM bound:
        # cache_chunks chunks of windows on the host (ChunkDataset) plus
        # their placed batches.
        cache_on = (self.train_cfg.cache_chunks > 0
                    and len(chunk_indices) <= self.train_cfg.cache_chunks)
        key = (id(dataset), tuple(chunk_indices))
        sink: Optional[List[Any]] = None  # a pass that is to be cached

        def host_batches() -> Iterable[Batch]:
            for idx in chunk_indices:
                yield from self.task.batches(dataset, idx)

        def open_pass():
            # the pass's input, opened by _run_batches under its
            # <phase>_pass_open span
            nonlocal sink
            if cache_on:
                from fmda_tpu.obs.registry import default_registry

                entry = self._placed_cache.get(key)
                # the entry pins its dataset, so a live hit can never be
                # an id()-reuse collision from a collected dataset
                hit = entry is not None and entry[0] is dataset
                result = "hit" if hit else "miss"
                default_registry().counter(
                    "train_placed_cache_total", result=result).inc()
                if account is not None:
                    account["cache"] = result
                if hit:
                    return (entry[1],)
            placed = self._place_batches(host_batches(), state)
            if not cache_on:
                return (placed,)
            sink = []

            def capturing() -> Iterable:
                for b in placed:
                    sink.append(b)
                    yield b

            return (capturing(),)

        out = self._run_batches(state, open_pass, rng, train, account)
        if sink is not None:
            self._placed_cache[key] = (dataset, sink)
            while len(self._placed_cache) > 4:  # train + val + headroom
                self._placed_cache.pop(next(iter(self._placed_cache)))
        return out

    def _run_batches(
        self,
        state: TrainState,
        batch_iterables,
        rng: Optional[jax.Array],
        train: bool,
        account: Optional[Dict[str, Any]] = None,
    ) -> Tuple[TrainState, EpochMetrics, np.ndarray]:
        """One pass over ``batch_iterables`` (the iterables, or a
        callable that opens them: a pass of ``fit`` opens its input
        under this pass's own ``<phase>_pass_open`` span).  Into
        ``account`` go the pass's boundaries on the host clock and its
        counts, read once a pass (fmda_tpu.train.epoch_account)."""
        import time as _time

        from fmda_tpu.obs.registry import default_registry
        from fmda_tpu.utils.tracing import span, step_annotation

        phase = "train" if train else "eval"
        reg = default_registry()
        step_counter = reg.counter("train_steps_total", phase=phase)
        call_counter = reg.counter("train_step_calls_total", phase=phase)
        stall = reg.histogram("train_input_stall_seconds")
        clock = _time.perf_counter
        # Host spans that tile one call into the compiled step, on the
        # profiler's clock (a flag test each when nothing traces;
        # docs/observability.md "Spans and scopes"): <phase>_next_batch,
        # <phase>, <phase>_fold; and once a pass, around the loop,
        # <phase>_pass_open, <phase>_pass_drain, <phase>_pass_publish,
        # each with the epoch's index.  What is left uncovered is the
        # loop's own Python.
        next_name, fold_name = phase + "_next_batch", phase + "_fold"
        epoch = self._epochs_run
        calls_before = call_counter.value
        # Each step's results are added to running on-device accumulators
        # inside the compiled step itself — the host never blocks
        # mid-pass, dispatches nothing but the step, and memory stays
        # O(1) instead of holding every batch's arrays live across an
        # epoch.  One device_get at the end drains the totals.
        with span(phase + "_pass_open", epoch=epoch):
            if callable(batch_iterables):
                batch_iterables = batch_iterables()
            totals = self.zero_totals()
        step_no = 0
        t_run = clock()
        for batches in batch_iterables:
            it = iter(batches)
            while True:
                # the input pipeline as the step loop meets it: the
                # cached list, or the prefetch queue (whose compose and
                # place spans nest in here); a pull is a group of
                # batches where _place_batches made groups
                t0 = clock()
                with span(next_name):
                    item = next(it, None)
                stall.observe(clock() - t0)
                if item is None:
                    break
                # the call into the jitted step: wrapper, dispatch,
                # whatever donation waits for, and letting go of the
                # donated state; the annotation carries the index of
                # the call's first step
                grouped = isinstance(item, BatchGroup)
                batch, live = item if grouped else (item, 1)
                count = (live,) if grouped else ()  # a group's argument
                with step_annotation(phase, step_no):
                    if train:
                        step = (self._train_group if grouped
                                else self._train_step)
                        state, out = step(state, totals, batch, *count, rng)
                    else:
                        step = (self._eval_group if grouped
                                else self._eval_step)
                        out = step(state.params, totals, batch, *count)
                step_counter.inc(live)
                call_counter.inc()
                step_no += live
                # what is left of the fold on the host: taking the new
                # totals for the old (the sum is in the compiled step)
                with span(fold_name):
                    totals = out
        if step_no == 0:
            log.warning(
                "pass produced no batches (source too short for "
                "window=%d/chunk_size=%d, or empty chunk split) — metrics "
                "are NaN", self.train_cfg.window, self.train_cfg.chunk_size,
            )
            drained = None
        else:
            # the one place the host waits for the device
            with span(phase + "_pass_drain", epoch=epoch):
                drained = jax.device_get(totals)
        t_drained = clock()
        with span(phase + "_pass_publish", epoch=epoch):
            published = None
            if drained is not None:
                published = self.task.publish(drained, phase, step_no)
            metrics = self.task.epoch_metrics(drained, step_no)
        if account is not None:
            account.update(
                t_run=t_run, t_drained=t_drained, t_published=clock(),
                steps=step_no,
                calls=int(call_counter.value - calls_before),
                published=published)
        return (state,) + metrics

    def _warn_if_norm_drifted(self, dataset: ChunkDataset) -> None:
        """Resume runs recompute normalization from the *current* source;
        if rows landed since the checkpoint was written, the serving stats
        (last-chunk min/max) shift under the restored params — loud, not
        silent."""
        saved = getattr(self, "_restored_norm", None)
        now = None if saved is None else self.task.norm_params(dataset)
        if now is None:
            return
        if not (
            np.allclose(saved.x_min, now.x_min)
            and np.allclose(saved.x_max, now.x_max)
        ):
            log.warning(
                "resuming on a source whose normalization stats differ from "
                "the checkpoint's (data changed since the save): inputs are "
                "rescaled relative to what the restored params saw"
            )

    def fit(
        self,
        source: FeatureSource,
        *,
        rng: Optional[jax.Array] = None,
        epochs: Optional[int] = None,
        bid_levels: int = 0,
        ask_levels: int = 0,
        initial_state: Optional[TrainState] = None,
        dataset: Optional[ChunkDataset] = None,
    ) -> Tuple[TrainState, Dict[str, List[EpochMetrics]], ChunkDataset]:
        """Train over a feature source; returns (state, history, dataset).

        ``initial_state`` (e.g. from :meth:`restore_state`) resumes
        mid-training instead of initialising fresh; ``epochs`` then means
        *additional* epochs to run.  ``dataset`` reuses a previously
        returned :class:`ChunkDataset` (it must wrap ``source``) instead
        of re-materializing it — a resumed fit then keeps every warm
        cache tier: host window gathers AND the placed device batches,
        which are keyed on dataset identity.
        """
        import time as _time

        from fmda_tpu.obs.registry import default_registry
        from fmda_tpu.utils.tracing import span

        # The epoch is accounted for whole (fmda_tpu.train.epoch_account):
        # fit_setup, then each pass's open / run / publish, then
        # fit_epoch_end, each part beginning on the clock read that ended
        # the one before it; a call's first epoch begins here.
        clock = _time.perf_counter
        t_start = clock()
        compiled = self._compile_marks()
        with span("fit_setup", epoch=self._epochs_run):
            tc = self.train_cfg
            rng = jax.random.PRNGKey(tc.seed) if rng is None else rng
            init_rng, step_rng = jax.random.split(rng)
            if dataset is None:
                dataset = self.task.dataset(
                    source, bid_levels=bid_levels, ask_levels=ask_levels)
            train_chunks, val_chunks, _ = dataset.split(
                tc.val_size, tc.test_size)
            state = (
                initial_state if initial_state is not None
                else self.init_state(init_rng)
            )
            if initial_state is not None:
                self._warn_if_norm_drifted(dataset)
            history: Dict[str, List[EpochMetrics]] = {"train": [], "val": []}
            reg = default_registry()
            epoch_hist = reg.histogram("train_epoch_seconds")
            epoch_counter = reg.counter("train_epochs_total")
        t_epoch = clock()
        for epoch in range(epochs if epochs is not None else tc.epochs):
            passes: Dict[str, Dict[str, Any]] = {"train": {}}
            state, train_metrics, _ = self._run_chunks(
                state, dataset, train_chunks, step_rng, True,
                passes["train"])
            history["train"].append(train_metrics)
            if val_chunks:
                passes["eval"] = {}
                _, val_metrics, _ = self._run_chunks(
                    state, dataset, val_chunks, None, False, passes["eval"])
            else:
                # continuous fine-tune rounds run val_size=0 (quality is
                # judged by the shadow gate, not a holdout) — NaN metrics
                # without the empty-pass warning
                val_metrics, _ = self.task.epoch_metrics(None, 0)
            with span("fit_epoch_end", epoch=self._epochs_run):
                history["val"].append(val_metrics)
                epoch_counter.inc()
                log.info(
                    "epoch %d: train loss=%.4f acc=%.4f hamming=%.4f | "
                    "val acc=%.4f hamming=%.4f",
                    epoch + 1,
                    train_metrics.loss,
                    train_metrics.accuracy,
                    train_metrics.hamming,
                    val_metrics.accuracy,
                    val_metrics.hamming,
                )
                t_end, compiled = self._end_epoch(
                    t_start, t_epoch, passes, compiled)
                epoch_hist.observe(t_end - t_epoch)
            t_start = t_epoch = t_end
        return state, history, dataset

    def _compile_marks(self) -> Tuple[Optional[int], Tuple]:
        """Where the compile accounts stand, for an epoch to be accounted
        by difference: the programs the tracked steps have compiled, all
        kinds together (None without jax's cache probe:
        ``compile_counts``), and the ledger's running totals of what jax
        compiled in the process (``CompileLedger.compile_parts_total``:
        six reads)."""
        counts = list(self.compile_counts.values())
        return (None if None in counts else sum(counts),
                self._train_step.ledger.compile_parts_total())

    def _end_epoch(self, t_start: float, t_epoch: float,
                   passes: Dict[str, Dict[str, Any]],
                   before: Tuple[Optional[int], Tuple]):
        """The epoch's last clock read and its ``train.epoch`` record
        (fmda_tpu.train.epoch_account); the next epoch's index.  Returns
        the read and the compile marks, for the next epoch to begin
        from."""
        import time as _time

        from fmda_tpu.train.epoch_account import COMPILE_PARTS, emit_epoch

        marks = self._compile_marks()
        t_end = _time.perf_counter()
        (compiled_before, parts_before), (compiled, parts) = before, marks
        emit_epoch(
            self._epochs_run, t_start, t_epoch, passes, t_end,
            warm=self._train_step.warm,
            compiles=(None if None in (compiled, compiled_before)
                      else compiled - compiled_before),
            compile_parts={
                key: now - was for key, now, was
                in zip(COMPILE_PARTS, parts, parts_before)})
        self._epochs_run += 1
        return t_end, marks

    def fit_multi(
        self,
        sources: Dict[str, FeatureSource],
        *,
        rng: Optional[jax.Array] = None,
        epochs: Optional[int] = None,
        bid_levels: int = 0,
        ask_levels: int = 0,
        mixed_batch_per_ticker: Optional[int] = None,
    ):
        """Multi-ticker shared-encoder training (north-star config 2):
        one model, batches interleaved across instruments, per-ticker
        chunk normalization.  Returns (state, history, MultiTickerDataset).

        ``mixed_batch_per_ticker=k`` switches from chunk-interleaved
        single-ticker batches to the north-star *mixed* composition: every
        step's batch concatenates ``k`` windows from EVERY ticker
        (``len(sources) * k`` rows/step — e.g. 50 x 16 = 800), so each
        gradient mixes all instruments and the device sees one big batch.
        """
        import time as _time

        from fmda_tpu.train.multiticker import MultiTickerDataset
        from fmda_tpu.utils.tracing import span

        clock = _time.perf_counter  # the epoch's account, as in fit
        t_start = clock()
        compiled = self._compile_marks()
        with span("fit_setup", epoch=self._epochs_run):
            tc = self.train_cfg
            rng = jax.random.PRNGKey(tc.seed) if rng is None else rng
            init_rng, step_rng = jax.random.split(rng)
            mtd = MultiTickerDataset(
                sources, tc.chunk_size, tc.window,
                bid_levels=bid_levels, ask_levels=ask_levels,
            )
            train_chunks, val_chunks, _ = mtd.splits(
                tc.val_size, tc.test_size)
            if mixed_batch_per_ticker:
                k = mixed_batch_per_ticker

                def host_batches(chunks):
                    # mixed composition is the expensive host stage (~12
                    # ms per 800-row batch): _place_batches runs it in
                    # the composer thread and double-buffers the transfer
                    for rc in mtd.rounds(chunks):
                        yield from mtd.mixed_batches(rc, k)
            else:
                def host_batches(chunks):
                    for t, c in chunks:
                        yield from mtd.batches(t, c, tc.batch_size)

            def placed(chunks, state):
                # one pipeline a pass, as _run_chunks has (opened under
                # the pass's own span): a group then runs on across a
                # chunk's end, in the pass's own order, and only the
                # pass's last group is padded
                return lambda: (
                    self._place_batches(host_batches(chunks), state),)

            state = self.init_state(init_rng)
            history: Dict[str, List[EpochMetrics]] = {"train": [], "val": []}
        t_epoch = clock()
        for epoch in range(epochs if epochs is not None else tc.epochs):
            passes: Dict[str, Dict[str, Any]] = {"train": {}, "eval": {}}
            state, train_metrics, _ = self._run_batches(
                state, placed(train_chunks, state), step_rng, True,
                passes["train"])
            history["train"].append(train_metrics)
            _, val_metrics, _ = self._run_batches(
                state, placed(val_chunks, state), None, False,
                passes["eval"])
            with span("fit_epoch_end", epoch=self._epochs_run):
                history["val"].append(val_metrics)
                log.info(
                    "multi epoch %d: train loss=%.4f acc=%.4f | "
                    "val acc=%.4f",
                    epoch + 1, train_metrics.loss, train_metrics.accuracy,
                    val_metrics.accuracy,
                )
                t_end, compiled = self._end_epoch(
                    t_start, t_epoch, passes, compiled)
            t_start = t_epoch = t_end
        return state, history, mtd

    def evaluate(
        self,
        state: TrainState,
        dataset: ChunkDataset,
        chunk_indices: Sequence[int],
    ) -> Tuple[EpochMetrics, np.ndarray]:
        """Eval pass (reference evaluate_model + confusion accumulation,
        biGRU_model.py:227-286)."""
        _, metrics, confusion = self._run_chunks(
            state, dataset, chunk_indices, None, train=False
        )
        return metrics, confusion


def imbalance_weights_from_source(source: FeatureSource) -> Tuple[np.ndarray, np.ndarray]:
    """Compute (weight, pos_weight) from the full target table — the
    notebook's ``SELECT SUM(target)/COUNT`` pass (cells 13-16)."""
    ids = range(1, len(source) + 1)
    y = source.fetch_targets(ids)
    counts = np.maximum(y.sum(axis=0), 1.0)
    return class_weights(counts, len(y))
