"""What a model family is trained on: its batches, its loss, the values
each step adds to the pass's totals, and what a pass publishes.

The trainer owns one step loop, one pair of compiled steps, the
placed-batch cache and the checkpoints; everything that differs between
a classifier of feature windows and a next-token decoder is asked of the
family's *task* (:func:`task_for`):

====================  ===================================================
``init_params``       fresh parameters (the dummy input is the task's)
``dataset``           chunks over the source, with the split
``batches``           fixed-shape :class:`~fmda_tpu.data.pipeline.Batch`
                      es of one chunk
``forward`` / ``loss``  inside the compiled step, under its ``forward`` /
                      ``loss`` scopes; ``loss_sums`` for accumulation
``step_values``       this step's entries of the totals (``metrics``
                      scope)
``zero_totals``       the totals at zero, as host arrays
``epoch_metrics``     the drained totals as :class:`EpochMetrics`
``publish``           counters written once a pass, at the drain
``fold``              (optional) the totals with a step's values, where
                      not every entry is a sum
``eval_loss``         (optional) ``loss`` as a validation or test pass
                      takes it, where the training objective has terms a
                      layer declares and such a pass leaves out
``after_update``      (optional) the step rule of parameters the
                      optimizer does not move
``feature_windows``   whether the source is a table of float features
                      (class weights, a drift profile, ``n_features``)
``norm_params``       the normalisation a checkpoint saves beside the
                      parameters (None where inputs are not normalised)
====================  ===================================================

The totals ride through the compiled steps as PR 27 made them: a step
gets the pass's totals and returns them with its own values added, so
the host fetches nothing until the pass drains.
"""

from __future__ import annotations

from typing import Any, Iterable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from fmda_tpu.config import ModelConfig, TrainConfig
from fmda_tpu.data.pipeline import (
    Batch, ChunkDataset, TokenBatches, TokenDataset, WindowBatches)
from fmda_tpu.models.decoder import COUNTS, model_counts, model_terms
from fmda_tpu.ops.metrics import multilabel_metrics
from fmda_tpu.train.losses import (
    chunked_next_token_loss, weighted_bce_sums, weighted_bce_with_logits)


class EpochMetrics(NamedTuple):
    """One pass's averages.  A classifier fills all four; the token task
    reports next-token accuracy, ``hamming = 1 - accuracy`` and an empty
    ``fbeta``."""

    loss: float
    accuracy: float
    hamming: float
    fbeta: np.ndarray  # (n_classes,)


class StepTotals(NamedTuple):
    """A classification pass's running sums of each step's loss and
    metrics, carried through the compiled steps.  One step from
    :meth:`Trainer.zero_totals` leaves that step's own values (``0 + v``
    is ``v`` exactly)."""

    loss: jax.Array
    accuracy: jax.Array
    hamming: jax.Array
    fbeta: jax.Array  # (n_classes,)
    confusion: jax.Array  # (n_classes, 2, 2) int32


class TokenTotals(NamedTuple):
    """A token pass's running totals: each step's mean loss, the tokens
    that counted and those predicted right, and what the layers counted,
    an attribute a name of :data:`fmda_tpu.models.decoder.COUNTS` (which
    says what each is; some are folded by ``max``: :data:`FOLDED_BY_MAX`),
    None where the model does not count it.  Last, the loss terms the
    layers declare (:func:`fmda_tpu.models.decoder.model_terms`): each
    step's value a layer, the mean over the step's sequences, as the
    objective took it; ``loss`` stays the next-token loss."""

    loss: jax.Array          # () float32
    tokens: jax.Array        # () int32
    correct: jax.Array       # () int32
    expert_pairs: Optional[jax.Array] = None  # (layers, held experts) int32
    dropped: Optional[jax.Array] = None       # () int32
    row_tiles_used: Optional[jax.Array] = None  # (layers,) int32
    layout_rounds: Optional[jax.Array] = None   # (layers,) int32
    sparse_keys_kept: Optional[jax.Array] = None   # (layers, 2) int32
    sparse_query_rows: Optional[jax.Array] = None  # (layers,) int32
    ssd_chunks: Optional[jax.Array] = None     # (layers,) int32
    ssd_positions: Optional[jax.Array] = None  # (layers,) int32
    router_load: Optional[jax.Array] = None    # (layers, moe_experts) int32
    latent_pairs: Optional[jax.Array] = None   # (layers,) int32
    router_bias_absmax: Optional[jax.Array] = None  # (layers,) float32
    hc_sum_error: Optional[jax.Array] = None        # (layers,) float32
    kda_chunks: Optional[jax.Array] = None     # (layers,) int32
    kda_positions: Optional[jax.Array] = None  # (layers,) int32
    kda_log_decay_absmax: Optional[jax.Array] = None  # (layers,) float32
    gdn_chunks: Optional[jax.Array] = None     # (layers,) int32
    gdn_positions: Optional[jax.Array] = None  # (layers,) int32
    gdn_log_decay_absmax: Optional[jax.Array] = None  # (layers,) float32
    gdn_beta_max: Optional[jax.Array] = None   # (layers,) float32
    seq_aux_loss: Optional[jax.Array] = None        # (layers,) float32


#: The :class:`TokenTotals` fields whose pass value is the largest of the
#: steps' values.
FOLDED_BY_MAX = tuple(
    name for name, count in COUNTS.items() if count.fold == "max")


#: A count published as it stands, and its series: a counter, or a gauge
#: where a pass folds it by ``max``.
PUBLISHED = {
    "row_tiles_used": "moe_row_tiles_used_total",
    "layout_rounds": "moe_layout_rounds_total",
    "sparse_query_rows": "sparse_query_rows_total",
    "ssd_chunks": "ssd_chunks_total",
    "ssd_positions": "ssd_positions_total",
    "latent_pairs": "attention_latent_pairs_total",
    "hc_sum_error": "hc_res_sum_error_max",
    "router_bias_absmax": "moe_router_bias_absmax",
    "kda_chunks": "kda_chunks_total",
    "kda_positions": "kda_positions_total",
    "kda_log_decay_absmax": "kda_log_decay_absmax",
    "gdn_chunks": "gdn_chunks_total",
    "gdn_positions": "gdn_positions_total",
    "gdn_log_decay_absmax": "gdn_log_decay_absmax",
    "gdn_beta_max": "gdn_beta_max",
}

#: A declared loss term's gauge: its mean a step over the pass, a layer.
PUBLISHED_TERMS = {"seq_aux_loss": "moe_seq_aux_loss"}


def keys_kept_counts(sparse_keys_kept) -> list:
    """The keys each layer kept, as whole Python numbers, from a
    :class:`TokenTotals`' split sums (a pass's count outgrows int32:
    16,384 tokens keep 31 M keys a layer and sequence)."""
    return [(int(hi) << 16) + int(lo)
            for hi, lo in np.asarray(sparse_keys_kept, np.int64)]


class WindowClassification:
    """Weighted BCE over the labels of a float feature window: the task
    of ``gru``, ``lstm``, ``attn`` and ``ssm``."""

    feature_windows = True

    def __init__(self, model_cfg: ModelConfig, train_cfg: TrainConfig, *,
                 weight=None, pos_weight=None) -> None:
        self.model_cfg, self.train_cfg = model_cfg, train_cfg
        self.weight, self.pos_weight = weight, pos_weight

    # -- outside the compiled step ------------------------------------------

    def init_params(self, model, rng: jax.Array) -> Any:
        dummy = jnp.zeros(
            (1, self.train_cfg.window, self.model_cfg.n_features),
            jnp.float32)
        return model.init({"params": rng}, dummy)["params"]

    def dataset(self, source, *, bid_levels: int = 0, ask_levels: int = 0
                ) -> ChunkDataset:
        tc = self.train_cfg
        return ChunkDataset(
            source, tc.chunk_size, tc.window, bid_levels=bid_levels,
            ask_levels=ask_levels, cache_chunks=tc.cache_chunks)

    def batches(self, dataset: ChunkDataset, chunk_idx: int
                ) -> Iterable[Batch]:
        return WindowBatches(dataset, chunk_idx, self.train_cfg.batch_size)

    def norm_params(self, dataset: ChunkDataset):
        return dataset.final_norm_params

    def zero_totals(self) -> StepTotals:
        n = self.model_cfg.output_size
        zero = np.zeros((), np.float32)
        return StepTotals(zero, zero, zero, np.zeros((n,), np.float32),
                          np.zeros((n, 2, 2), np.int32))

    def epoch_metrics(self, totals: Optional[StepTotals], steps: int
                      ) -> Tuple[EpochMetrics, np.ndarray]:
        n = self.model_cfg.output_size
        if totals is None:  # a pass with no batch
            nan = float("nan")
            return (EpochMetrics(nan, nan, nan, np.zeros(n)),
                    np.zeros((n, 2, 2), np.int64))
        loss_sum, acc_sum, ham_sum, fbeta_sum, confusion_total = totals
        return EpochMetrics(
            loss=float(loss_sum) / steps,
            accuracy=float(acc_sum) / steps,
            hamming=float(ham_sum) / steps,
            fbeta=np.asarray(fbeta_sum) / steps,
        ), np.asarray(confusion_total, np.int64)

    def publish(self, totals: StepTotals, phase: str, steps: int) -> None:
        """Nothing beyond what the step loop counts itself."""

    # -- inside the compiled step ---------------------------------------------

    def forward(self, model, params, batch: Batch, rng: Optional[jax.Array]):
        if rng is None:
            return model.apply({"params": params}, batch.x)
        return model.apply({"params": params}, batch.x, deterministic=False,
                           rngs={"dropout": rng})

    def loss(self, params, out, batch: Batch):
        """``(mean loss, what step_values reads)``."""
        return weighted_bce_with_logits(
            out, batch.y, weight=self.weight, pos_weight=self.pos_weight,
            example_mask=batch.mask), out

    def loss_sums(self, params, out, batch: Batch):
        """``(loss sum, count, aux)`` of one microbatch."""
        s, count = weighted_bce_sums(
            out, batch.y, weight=self.weight, pos_weight=self.pos_weight,
            example_mask=batch.mask)
        return s, count, out

    def merge_micro(self, aux_k):
        """The microbatches' logits as the full batch's."""
        return aux_k.reshape((-1,) + aux_k.shape[2:])

    def step_values(self, loss, aux, batch: Batch) -> StepTotals:
        tc = self.train_cfg
        metrics = multilabel_metrics(
            aux, batch.y, threshold=tc.prob_threshold, beta=tc.fbeta_beta,
            example_mask=batch.mask)
        return StepTotals(loss, *metrics)


class NextToken:
    """Next-token cross-entropy over the held vocabulary, per-token
    mask: the task of ``decoder``."""

    feature_windows = False

    def __init__(self, model_cfg: ModelConfig, train_cfg: TrainConfig, **_
                 ) -> None:
        self.model_cfg, self.train_cfg = model_cfg, train_cfg

    # -- outside the compiled step ------------------------------------------

    def init_params(self, model, rng: jax.Array) -> Any:
        # eight positions: parameters do not depend on the length, and an
        # eager init at the trained length would run every layer op by op
        from fmda_tpu.obs.device import tracked_jit

        dummy = jnp.zeros((1, 8), jnp.int32)
        return tracked_jit(
            lambda r: model.init({"params": r}, dummy)["params"],
            name="decoder_init")(rng)

    def dataset(self, source, **_) -> TokenDataset:
        tc = self.train_cfg
        if source.vocab_size > self.model_cfg.vocab_size:
            raise ValueError(
                f"the source's ids run to {source.vocab_size - 1}, the "
                f"model holds {self.model_cfg.vocab_size}")
        return TokenDataset(source, tc.chunk_size, tc.window)

    def batches(self, dataset: TokenDataset, chunk_idx: int
                ) -> Iterable[Batch]:
        return TokenBatches(dataset, chunk_idx, self.train_cfg.batch_size)

    def norm_params(self, dataset: TokenDataset) -> None:
        """Token ids are not normalised."""
        return None

    def zero_totals(self) -> TokenTotals:
        zero = np.zeros((), np.int32)
        depth = len(self.model_cfg.layer_layout)
        return TokenTotals(np.zeros((), np.float32), zero, zero, **{
            name: np.zeros(c.shape, c.count.dtype)
            for name, c in model_counts(self.model_cfg).items()}, **{
            name: np.zeros((depth,), np.float32)
            for name in model_terms(self.model_cfg)})

    def epoch_metrics(self, totals: Optional[TokenTotals], steps: int
                      ) -> Tuple[EpochMetrics, np.ndarray]:
        confusion = np.zeros((0, 2, 2), np.int64)
        if totals is None:
            nan = float("nan")
            return EpochMetrics(nan, nan, nan, np.zeros(0)), confusion
        accuracy = float(totals.correct) / max(int(totals.tokens), 1)
        return EpochMetrics(
            loss=float(totals.loss) / steps, accuracy=accuracy,
            hamming=1.0 - accuracy, fbeta=np.zeros(0)), confusion

    def publish(self, totals: TokenTotals, phase: str, steps: int
                ) -> Optional[dict]:
        """The pass's token count and what :func:`model_counts` declares
        its layers count, from the drained totals of its ``steps`` steps,
        a series a layer (docs/observability.md "Spans and scopes"); and
        each declared loss term's mean a step, a gauge a layer.  Returns
        what of it the epoch's ``train.epoch`` record carries for the
        pass: each term summed over its layers, a step (None: nothing)."""
        from fmda_tpu.obs.registry import default_registry
        from fmda_tpu.ops.moe import layout_tiles

        reg, tc, mc = default_registry(), self.train_cfg, self.model_cfg
        declared = model_counts(mc)
        if phase == "train":
            reg.counter("train_tokens_total").inc(int(totals.tokens))

        def by_layer(name):
            """``(labels, value)`` of each layer that counts ``name``."""
            if name not in declared:
                return []
            values = np.asarray(getattr(totals, name))
            return [(dict(layer=str(layer), phase=phase), values[layer])
                    for layer in declared[name].layers]

        for name, metric in PUBLISHED.items():
            for labels, value in by_layer(name):
                if name in FOLDED_BY_MAX:
                    reg.gauge(metric, **labels).set(float(value))
                else:
                    reg.counter(metric, **labels).inc(int(value))
        if "expert_pairs" in declared:
            reg.counter("moe_pairs_dropped_total").inc(int(totals.dropped))
        for labels, pairs in by_layer("expert_pairs"):
            reg.counter("moe_pairs_held_total", **labels).inc(
                int(pairs.sum()))
            reg.gauge("moe_expert_pairs_max", **labels).set(int(pairs.max()))
        # a layer's calls a step: one, or one a microbatch it accumulates
        passes = tc.accum_steps if phase == "train" else 1
        for labels, rounds in by_layer("layout_rounds"):
            # rounds over calls: 1.0 where every call fitted the layout once;
            # tiles used over tiles laid out: the share that held a group
            reg.counter("moe_layer_calls_total", **labels).inc(steps * passes)
            reg.counter("moe_row_tiles_layout_total", **labels).inc(
                int(rounds) * layout_tiles(
                    tc.batch_size // passes * tc.window * mc.moe_top_k,
                    mc.experts_held[1], mc.moe_experts))
        # a learned-sparse layer's selection: kept / (the rows' causal
        # pairs) is the share of the triangle the heads attend over
        for labels, halves in by_layer("sparse_keys_kept"):
            reg.counter("sparse_keys_kept_total", **labels).inc(
                keys_kept_counts([halves])[0])
        if "ssd_chunks" in declared:
            # the carried states one sequence leaves (a matrix a head and
            # chunk, float32)
            reg.gauge("ssd_state_bytes").set(
                -(-tc.window // mc.ssm_chunk) * mc.ssm_heads
                * mc.ssm_head_dim * mc.ssm_state * 4)
        for labels, load in by_layer("router_load"):
            for expert, pairs in enumerate(load):
                reg.counter("moe_router_load_total", expert=str(expert),
                            **labels).inc(int(pairs))
        record = {}
        for name, layers in model_terms(mc).items():
            a_step = np.asarray(getattr(totals, name), np.float64) / steps
            for layer in layers:
                reg.gauge(PUBLISHED_TERMS[name], layer=str(layer),
                          phase=phase).set(float(a_step[layer]))
            record[name] = float(a_step.sum())
        return record or None

    # -- inside the compiled step ---------------------------------------------

    def fold(self, totals: TokenTotals, values: TokenTotals) -> TokenTotals:
        """The pass's totals with one step's values: sums, but for the
        fields of :data:`FOLDED_BY_MAX`."""
        worst = {name: jnp.maximum(getattr(totals, name),
                                   getattr(values, name))
                 for name in FOLDED_BY_MAX
                 if getattr(values, name) is not None}
        return jax.tree.map(jnp.add, totals, values)._replace(**worst)

    def after_update(self, params, aux):
        """The parameters after the optimizer's update and the step rule
        of what it does not move: each expert layer's selection bias goes
        up by ``moe_bias_rate`` where the step sent its expert fewer pairs
        than the mean over all experts, and down where more."""
        counts, rate = aux[2], self.model_cfg.moe_bias_rate
        if "router_load" not in counts or not rate:
            return params
        params = dict(params)
        for layer, load in enumerate(counts["router_load"]):
            block = params.get(f"block_{layer}", {})
            if "router_bias" not in block:
                continue
            load = load.astype(jnp.float32)
            params[f"block_{layer}"] = dict(
                block, router_bias=block["router_bias"] + rate * jnp.sign(
                    jnp.mean(load) - load))
        return params

    def forward(self, model, params, batch: Batch, rng: Optional[jax.Array]):
        del rng  # the family has no dropout
        return model.apply({"params": params}, batch.x, method="features")

    def loss_sums(self, params, out, batch: Batch, terms: bool = True):
        """``(what is differentiated, as a sum; its count; aux)``: the
        next-token loss's sum over the counted tokens, and with ``terms``
        each loss term the layers declare
        (:func:`fmda_tpu.models.decoder.model_terms`), a sequence's value
        times the tokens it counts: over the count, the mean over the
        step's sequences (every sequence of a token source counts all of
        its tokens or, as padding, none).  ``aux``'s stats then carry,
        as sums, each term a layer and the next-token loss alone."""
        hidden, stats = out
        mc = self.model_cfg
        # a tied head is the embedding, transposed: the leaf's gradient
        # comes from both uses
        head = params["embed"].T if mc.tie_embeddings else params["head"]
        s, tokens, correct = chunked_next_token_loss(
            hidden.reshape(-1, hidden.shape[-1]), head,
            batch.y.reshape(-1), batch.mask.reshape(-1),
            chunk=mc.loss_chunk, logits_scaling=mc.logits_scaling)
        declared = model_terms(mc)
        if declared:
            with jax.named_scope("loss_terms"):
                counted = jnp.sum(batch.mask, axis=-1)  # (B,): a sequence's
                weighed = {name: jnp.sum(stats[name] * counted, axis=-1)
                           for name in declared}        # (layers,) each
                stats = {**stats, **weighed, "next_token_sum": s}
                if terms:
                    s = s + sum(jnp.sum(v) for v in weighed.values())
        return s, tokens.astype(jnp.float32), (tokens, correct, stats)

    def loss(self, params, out, batch: Batch, terms: bool = True):
        s, count, aux = self.loss_sums(params, out, batch, terms)
        return s / jnp.maximum(count, 1.0), aux

    def eval_loss(self, params, out, batch: Batch):
        """The next-token loss alone: a validation or test pass leaves
        the declared terms out of its loss (their values are still
        folded and published, by phase)."""
        return self.loss(params, out, batch, terms=False)

    def merge_micro(self, aux_k):
        """Counts add over the microbatches; the largest of what a pass
        folds by ``max`` (:data:`FOLDED_BY_MAX`)."""
        tokens, correct, counts = jax.tree.map(
            lambda a: jnp.sum(a, axis=0), aux_k)
        worst = {name: jnp.max(aux_k[2][name], axis=0)
                 for name in FOLDED_BY_MAX if name in counts}
        return tokens, correct, {**counts, **worst}

    def step_values(self, loss, aux, batch: Batch) -> TokenTotals:
        tokens, correct, counts = aux
        if "next_token_sum" in counts:
            # the reported loss stays the next-token loss; the declared
            # terms go beside it, each the step's mean a layer
            counts = dict(counts)
            n = jnp.maximum(tokens.astype(jnp.float32), 1.0)
            loss = counts.pop("next_token_sum") / n
            for name in model_terms(self.model_cfg):
                counts[name] = counts[name] / n
        return TokenTotals(loss, tokens, correct, **counts)


def task_class(model_cfg: ModelConfig):
    """The task of ``model_cfg.cell``'s family, as a class."""
    return NextToken if model_cfg.cell == "decoder" else WindowClassification


def task_for(model_cfg: ModelConfig, train_cfg: TrainConfig, *,
             weight=None, pos_weight=None):
    return task_class(model_cfg)(
        model_cfg, train_cfg, weight=weight, pos_weight=pos_weight)
