"""Typed configuration for the fmda_tpu framework.

Re-designs the reference's flat constants module (``/root/reference/config.py``)
as frozen dataclasses while keeping its single load-bearing property: the
**config → schema codegen**.  In the reference, changing ``bid_levels`` or
``event_list`` reshapes the Kafka message schemas, the Spark streaming schemas,
the MariaDB DDL, and the training feature set (``create_database.py:29-70``,
``spark_consumer.py:241-291``).  Here the same knobs drive
:meth:`FeatureConfig.table_columns` / :meth:`FeatureConfig.x_fields`, which
every other layer (stream engine, warehouse, data pipeline, model input width,
serving) derives its shapes from.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple


# ---------------------------------------------------------------------------
# Bus (message transport) — replaces the reference's Kafka topic layout
# (config.py:15: vix, volume, cot, ind, deep, predict_timestamp, prediction).
# ---------------------------------------------------------------------------

TOPIC_VIX = "vix"
TOPIC_VOLUME = "volume"
TOPIC_COT = "cot"
TOPIC_IND = "ind"
TOPIC_DEEP = "deep"
TOPIC_PREDICT_TIMESTAMP = "predict_timestamp"
TOPIC_PREDICTION = "prediction"
#: Fleet-serving results (fmda_tpu.runtime): one topic, per-session
#: consumption keyed on the message's ``session`` field.
TOPIC_FLEET_PREDICTION = "fleet_prediction"
#: Multi-host fleet control plane (fmda_tpu.fleet): worker hello/
#: heartbeat/goodbye, ownership-table announcements, migrated session
#: state.  Not in DEFAULT_TOPICS — only fleet topologies carry it
#: (fleet_topics adds it alongside the per-worker inboxes).
TOPIC_FLEET_CONTROL = "fleet_control"
#: Per-worker tick-inbox topic prefix (fmda_tpu.fleet): the router
#: publishes a worker's opens/ticks/closes/drains to
#: ``fleet_ticks_<worker_id>`` in routing order — the inbox's FIFO
#: offsets ARE the ordering guarantee the migration protocol leans on.
TOPIC_FLEET_TICKS_PREFIX = "fleet_ticks_"

DEFAULT_TOPICS: Tuple[str, ...] = (
    TOPIC_VIX,
    TOPIC_VOLUME,
    TOPIC_COT,
    TOPIC_IND,
    TOPIC_DEEP,
    TOPIC_PREDICT_TIMESTAMP,
    TOPIC_PREDICTION,
    TOPIC_FLEET_PREDICTION,
)


def fleet_worker_topic(worker_id: str) -> str:
    """The tick-inbox topic of one fleet worker."""
    return TOPIC_FLEET_TICKS_PREFIX + worker_id


def fleet_topics(worker_ids) -> Tuple[str, ...]:
    """Every extra topic a fleet topology needs on its bus: the control
    plane plus one inbox per worker (append to ``DEFAULT_TOPICS`` when
    constructing the topology's bus)."""
    return (TOPIC_FLEET_CONTROL,) + tuple(
        fleet_worker_topic(w) for w in worker_ids)


@dataclass(frozen=True)
class BusConfig:
    """Message-bus layout (ref: config.py:15 ``kafka_config``)."""

    topics: Tuple[str, ...] = DEFAULT_TOPICS
    #: Ring-buffer capacity per topic (records) for the native bus backend.
    capacity: int = 1 << 16
    #: External Kafka brokers, only used by the optional Kafka adapter.
    servers: Tuple[str, ...] = ("localhost:9092",)


# ---------------------------------------------------------------------------
# Warehouse
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class WarehouseConfig:
    """Warehouse backend (ref: MariaDB, config.py:21-28).

    The framework-owned default is an embedded SQLite database (zero external
    processes); a MySQL/MariaDB adapter with the reference's exact DDL can be
    selected with ``backend="mysql"`` when ``mysql.connector`` is installed.
    """

    backend: str = "sqlite"
    path: str = ":memory:"  # sqlite path or file
    database_name: str = "stock_data"
    table_name: str = "stock_data_joined"
    #: Write-ahead journal file for warehouse-outage survival
    #: (fmda_tpu.stream.journal.BufferedWarehouse): failed landings
    #: spill here durably and a backfill loop drains them on recovery,
    #: idempotent on timestamp.  None disables the buffer (a failed
    #: insert raises through the engine step, pre-ISSUE-10 behavior).
    journal_path: Optional[str] = None
    #: Bound on journaled rows; overflow sheds the oldest, counted.
    journal_bound: int = 65536
    #: Journal record layout: ``jsonl`` (one JSON line per row — the
    #: human-inspectable debug format) or ``binary`` (length-prefixed
    #: packed-column codec frames, fmda_tpu.stream.codec — the same
    #: layout the binary wire speaks; no text round trip on the landing
    #: hot path).  Recovery auto-detects per record, so flipping this
    #: never strands an existing journal.
    journal_format: str = "jsonl"
    # MySQL parity fields (unused by the sqlite backend)
    user: str = "admin"
    password: str = "admin"
    hostname: str = "localhost"
    port: int = 3306


# ---------------------------------------------------------------------------
# Feature configuration + schema codegen
# ---------------------------------------------------------------------------

DEFAULT_EVENT_LIST: Tuple[str, ...] = (
    "Crude Oil Inventories",
    "ISM Non-Manufacturing PMI",
    "ISM Non-Manufacturing Employment",
    "Services PMI",
    "ADP Nonfarm Employment Change",
    "Core CPI",
    "Fed Interest Rate Decision",
    "Building Permits",
    "Core Retail Sales",
    "Retail Sales",
    "JOLTs Job Openings",
    "Nonfarm Payrolls",
    "Unemployment Rate",
)

EVENT_VALUES: Tuple[str, ...] = ("Actual", "Prev_actual_diff", "Forc_actual_diff")

#: OHLCV column names as used by the reference end to end (the Alpha Vantage
#: JSON keys ``1. open`` etc. become ``1_open`` after key sanitisation,
#: getMarketData.py:240).
VOLUME_COLUMNS: Tuple[str, ...] = (
    "1_open",
    "2_high",
    "3_low",
    "4_close",
    "5_volume",
    "wick_prct",
)

COT_GROUPS: Tuple[str, ...] = ("Asset", "Leveraged")
COT_VALUES: Tuple[str, ...] = (
    "long_pos",
    "long_pos_change",
    "long_open_int",
    "short_pos",
    "short_pos_change",
    "short_open_int",
)

TARGET_COLUMNS: Tuple[str, ...] = ("up1", "up2", "down1", "down2")


def sanitize_event(event_name: str) -> str:
    """Event name → column stem (ref: config.py:58)."""
    return event_name.replace(" ", "_").replace("-", "_")


@dataclass(frozen=True)
class FeatureConfig:
    """Feature-engineering knobs (ref: config.py:31-65) + schema codegen.

    The derived-feature parameters replicate the reference's SQL views
    (create_database.py:76-190), including its quirks: the stochastic
    oscillator and ATR windows are written as ``14 PRECEDING AND CURRENT ROW``
    — i.e. **15-row** windows — while the MA views use ``period-1 PRECEDING``
    (= ``period``-row windows).
    """

    get_cot: bool = True
    get_vix: bool = True
    #: Ticker whose OHLCV volume feed is ingested, or None to disable
    #: (ref: config.py:33 ``get_stock_volume = 'SPY'``).
    get_stock_volume: Optional[str] = "SPY"

    bid_levels: int = 7
    ask_levels: int = 7

    volume_ma_periods: Tuple[int, ...] = (6, 20)
    price_ma_periods: Tuple[int, ...] = (20,)
    delta_ma_periods: Tuple[int, ...] = (12,)

    bollinger_period: int = 20
    bollinger_std: float = 2.0

    stochastic_oscillator: bool = True
    #: ``N PRECEDING`` counts — the effective rolling window is N+1 rows.
    stoch_preceding: int = 14
    atr_preceding: int = 14

    event_list: Tuple[str, ...] = DEFAULT_EVENT_LIST

    # Target construction (create_database.py:176-190)
    target_n1: float = 1.5
    target_n2: float = 3.0
    target_lead1: int = 8
    target_lead2: int = 15

    #: Stream alignment: floor timestamps to this many seconds
    #: (spark_consumer.py:111 — 5 minutes) and join feeds whose timestamps lie
    #: within ``join_tolerance_s`` after the order-book timestamp
    #: (spark_consumer.py:439-443 — 3 minutes).
    floor_s: int = 5 * 60
    join_tolerance_s: int = 3 * 60
    watermark_s: int = 5 * 60

    # -- schema codegen -----------------------------------------------------

    @property
    def event_list_repl(self) -> Tuple[str, ...]:
        return tuple(sanitize_event(e) for e in self.event_list)

    def empty_ind_message(self) -> dict:
        """Economic-indicator message template (ref: config.py:58-65)."""
        msg: dict = {"Timestamp": 0}
        for event in self.event_list_repl:
            msg[event] = {value: 0 for value in EVENT_VALUES}
        return msg

    def deep_columns(self) -> Tuple[str, ...]:
        """Order-book feature columns landed in the warehouse.

        Mirrors the reference DDL order (create_database.py:29-46): sizes for
        all levels, rebased prices for levels 1.. (level-0 rebased prices are
        identically zero and dropped, spark_consumer.py:397-400), then the
        microstructure scalars and calendar one-hots.
        """
        cols = []
        cols += [f"bid_{i}_size" for i in range(self.bid_levels)]
        cols += [f"bid_{i}" for i in range(1, self.bid_levels)]
        cols += [f"ask_{i}_size" for i in range(self.ask_levels)]
        cols += [f"ask_{i}" for i in range(1, self.ask_levels)]
        cols += [
            "bids_ord_WA",
            "asks_ord_WA",
            "vol_imbalance",
            "delta",
            "micro_price",
            "spread",
            "session_start",
            "day_1",
            "day_2",
            "day_3",
            "day_4",
            "week_1",
            "week_2",
            "week_3",
            "week_4",
        ]
        return tuple(cols)

    def vix_columns(self) -> Tuple[str, ...]:
        return ("VIX",) if self.get_vix else ()

    def volume_columns(self) -> Tuple[str, ...]:
        return VOLUME_COLUMNS if self.get_stock_volume else ()

    def cot_columns(self) -> Tuple[str, ...]:
        if not self.get_cot:
            return ()
        return tuple(f"{g}_{v}" for g in COT_GROUPS for v in COT_VALUES)

    def ind_columns(self) -> Tuple[str, ...]:
        return tuple(
            f"{event}_{value}"
            for event in self.event_list_repl
            for value in EVENT_VALUES
        )

    def table_columns(self) -> Tuple[str, ...]:
        """All feature columns of the joined warehouse table, in DDL order
        (create_database.py:69-70), excluding ID and Timestamp."""
        return (
            self.deep_columns()
            + self.vix_columns()
            + self.volume_columns()
            + self.cot_columns()
            + self.ind_columns()
        )

    def derived_columns(self) -> Tuple[str, ...]:
        """Windowed-indicator columns (the reference's SQL views), in the
        order the reference's ``join_statement`` concatenates them
        (create_database.py:240-241: BB, vol_MA, price_MA, delta_MA, stoch,
        ATR, price_change).

        Every OHLC-derived view requires the volume feed; with
        ``get_stock_volume`` disabled only the book-derived ``delta_MA``
        survives (the reference would simply crash building its views
        without the OHLCV columns — here the schema narrows instead).
        """
        has_ohlc = bool(self.get_stock_volume)
        cols = []
        if has_ohlc and self.bollinger_period and self.bollinger_std:
            cols += ["upper_BB_dist", "lower_BB_dist"]
        if has_ohlc:
            cols += [f"vol_MA{p}" for p in self.volume_ma_periods]
            cols += [f"price_MA{p}" for p in self.price_ma_periods]
        cols += [f"delta_MA{p}" for p in self.delta_ma_periods]
        if has_ohlc and self.stochastic_oscillator:
            cols += ["stoch"]
        if has_ohlc:
            cols += ["ATR", "price_change"]
        return tuple(cols)

    @property
    def max_lookback(self) -> int:
        """Longest trailing frame any derived view needs (rows)."""
        frames = [2]  # LAG(close, 1) needs 2 rows
        if self.get_stock_volume:
            if self.bollinger_period and self.bollinger_std:
                frames.append(self.bollinger_period)
            frames.extend(self.volume_ma_periods)
            frames.extend(self.price_ma_periods)
            if self.stochastic_oscillator:
                frames.append(self.stoch_preceding + 1)
            frames.append(self.atr_preceding + 1)
        frames.extend(self.delta_ma_periods)
        return max(frames)

    @property
    def max_lead(self) -> int:
        """Longest LEAD the target view uses (rows)."""
        return max(self.target_lead1, self.target_lead2)

    def x_fields(self) -> Tuple[str, ...]:
        """The model's input-feature schema: table columns followed by derived
        columns — the column set of the reference's ``join_statement``
        (create_database.py:240-258; 108 features with default config)."""
        return self.table_columns() + self.derived_columns()

    @property
    def n_features(self) -> int:
        return len(self.x_fields())


# ---------------------------------------------------------------------------
# Model / training / parallelism
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ModelConfig:
    """Hyperparameters of every model family; ``cell`` names the family
    and decides which of the fields below it reads.

    The window classifiers (``gru``, ``lstm``, ``attn``, ``ssm``) share
    the reference's protocol (ref: biGRU_model.py:32; notebook cell 29):
    float feature windows in, ``output_size`` labels out.
    ``n_features=None`` means "derive from the feature schema" — resolved
    by :class:`FrameworkConfig` so the model width can never silently
    diverge from what the data pipeline emits.  The token ``decoder``
    reads ``vocab_size`` instead, and the ``n_kv_heads`` .. ``loss_chunk``
    group at the end (docs/training.md "The decoder family").
    """

    hidden_size: int = 32
    n_features: Optional[int] = None
    output_size: int = len(TARGET_COLUMNS)
    n_layers: int = 1
    dropout: float = 0.5
    spatial_dropout: bool = True
    bidirectional: bool = True
    #: Sequence-core family: "gru" (the reference's model), "lstm" (same
    #: head/protocol over fmda_tpu.ops.lstm — the torch user's one-line
    #: nn.GRU -> nn.LSTM swap), "attn" (temporal transformer encoder
    #: over fmda_tpu.ops.attention, the ring-shardable long-context
    #: core), or "ssm" (gated linear recurrence over fmda_tpu.ops.ssm —
    #: trains in the parallel associative-scan mode, serves from a
    #: constant-size O(1) cache with no ring and no per-tick matmul;
    #: docs/runtime.md "The SSM cell family").
    cell: str = "gru"
    #: Attention heads for cell="attn"; must divide hidden_size.
    n_heads: int = 4
    #: Causal (streaming-safe) attention for cell="attn"; the default
    #: mirrors the reference's bidirectional window encoder.
    attn_causal: bool = False
    #: Residual/internal dropout for cell="attn" encoder blocks; None
    #: (the default) falls back to ``dropout``.  Separate knob because
    #: the protocol's dropout=0.5 is the INPUT spatial dropout
    #: (biGRU_model.py:87-94) — the reference's 1-layer GRU core itself
    #: carries no dropout, so 0.5 on every transformer residual
    #: over-regularises the attn family relative to its siblings.  The
    #: family-shootout sweep measured 0.1 as the winner
    #: (RESULTS_FAMILIES.md: test accuracy 0.237 vs 0.193 at 0.5, best
    #: val + backtest edge; 0.0 scores higher on raw test accuracy but
    #: halves the backtest edge) — the shootout/experiment configs set
    #: it explicitly (experiments/family_shootout.py --attn-dropout).
    attn_dropout: Optional[float] = None
    #: cell="ssm": initial per-channel zero-input state-decay range —
    #: each channel's learned decay offset ``a_base`` is initialised so
    #: ``sigmoid(a_base)`` is uniform in this range (the LRU-style
    #: long-memory ring init: channels start spread from "remember ~10
    #: ticks" to "remember ~1000").
    ssm_decay_range: Tuple[float, float] = (0.9, 0.999)
    #: cell="ssm": initial (fast, slow) head-EMA decay rates — the
    #: family's O(1) replacement for the ring head's max/mean window
    #: pools; per-channel and learned from these starting points.  The
    #: default is the shootout sweep's winner (RESULTS_FAMILIES.md: test
    #: accuracy 0.226 vs 0.207 at (0.5, 0.95); the slower fast-EMA
    #: keeps the head's short-horizon pool from tracking tick noise).
    ssm_ema_init: Tuple[float, float] = (0.6, 0.98)
    #: Compute dtype for the GRU/head; params are kept in float32.
    dtype: str = "float32"
    #: Use the fused Pallas scan cell on TPU (falls back to lax.scan
    #: elsewhere).  True means "kernel where it fits": selection is
    #: additionally gated per shape on the kernel's VMEM feasibility
    #: (fmda_tpu.ops.pallas_gru.kernel_supported) — at MXU-wide hidden
    #: sizes the model auto-selects lax.scan, whose per-step matmul is
    #: MXU-shaped there anyway.  Default off: the flagship default path
    #: must be the one exercised everywhere; the decoder cell's config
    #: (benchmark/configs/), ``chip_smoke.py`` and the TPU-gated tests
    #: opt in explicitly.  No cell of record has yet shown a recurrent
    #: kernel winning: ROADMAP S3, then D2 decides flag and kernels.
    use_pallas: bool = False
    #: Rematerialise in backward (jax.checkpoint): the recurrence of the
    #: recurrent families, each whole block of ``attn`` and ``decoder`` —
    #: trades recompute FLOPs for HBM; enable for long context.
    remat: bool = False
    # -- cell="decoder": a causal token decoder with routed experts ------
    #: Token ids the embedding and the untied head hold: the whole
    #: vocabulary, or the slice of it one chip of a vocabulary-parallel
    #: group holds (a sliced vocabulary is a smaller vocabulary: ids,
    #: logits and loss are over the slice).
    vocab_size: int = 0
    #: Key/value heads; ``n_heads`` query heads share them in runs of
    #: ``n_heads // n_kv_heads`` (grouped-query attention).
    n_kv_heads: int = 0
    #: Width of one attention head (queries are ``n_heads * head_dim``
    #: wide, whatever ``hidden_size`` is).
    head_dim: int = 0
    #: One entry per layer (its length is the depth; ``n_layers`` is not
    #: read): 1 = rotary positions on q and k and a causal window of
    #: ``sliding_window`` keys, 0 = no positional encoding and the whole
    #: causal past, 2 = a learned-sparse layer: rotary positions and an
    #: RMSNorm per head on q and k, an indexer that picks ``indexer_topk``
    #: keys of the causal past a query (ops/sparse_attention.py), and the
    #: router placed *after* attention, reading the expert block's
    #: normalised input; 3 = a state-space layer: no attention, a scan
    #: over matrix-valued state (the ``ssm_*`` group below); 4 = a latent-
    #: attention layer (the ``q_lora_rank`` group below); 5 = a delta-rule
    #: layer with a decay a channel (the ``kda_*`` group below); 6 = a
    #: gated delta rule, one decay a head (the ``gdn_*`` group below).
    #: Kinds 4 and 5 state their heads' widths themselves and may share a
    #: model, in any order; they do not mix with kinds 0..3 and 6.
    layer_layout: Tuple[int, ...] = ()
    sliding_window: int = 4096
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    #: Width of the router: experts a token is routed over, on whatever
    #: chip they live.
    moe_experts: int = 0
    #: Experts a token is sent to; their gates are renormalised to one.
    moe_top_k: int = 0
    #: Hidden width of one expert (``hidden -> moe_ffn_size -> hidden``).
    moe_ffn_size: int = 0
    #: The gate's activation in an expert's gated product: "relu"
    #: (ReGLU) or "silu" (SwiGLU).
    hidden_act: str = "relu"
    #: Layout 2's indexer: ``indexer_heads`` query heads of
    #: ``indexer_head_dim`` on one key head, and the keys a query keeps.
    indexer_heads: int = 0
    indexer_head_dim: int = 0
    indexer_topk: int = 0
    #: ``(first, count)``: the experts this chip holds and computes
    #: (ops/moe.py).  ``(0, moe_experts)`` is the uncut layer.
    experts_held: Tuple[int, int] = (0, 0)
    #: Tokens whose logits exist at a time in the loss: the cross-entropy
    #: is summed chunk by chunk (recomputed in backward), the same sum as
    #: over (tokens, vocab_size) at once.
    loss_chunk: int = 1024
    #: ``layer_layout`` 3, a state-space layer in place of attention
    #: (ops/ssd.py): ``ssm_heads`` heads of ``ssm_head_dim`` channels, each
    #: carrying a ``(ssm_head_dim, ssm_state)`` matrix; one group of
    #: ``ssm_state`` input and output coefficients shared by the heads; a
    #: causal depthwise convolution of ``ssm_conv`` taps in front; the
    #: scan taken ``ssm_chunk`` positions at a time.
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_conv: int = 0
    ssm_chunk: int = 0
    #: ``layer_layout`` 5, a delta-rule layer in place of attention
    #: (ops/kda.py): ``kda_heads`` heads, each carrying a ``(kda_head_dim,
    #: kda_head_dim)`` float32 state that every position decays by a
    #: vector (a factor a key channel) and corrects by a rank-one step;
    #: queries, keys and values ``kda_head_dim`` wide behind a causal
    #: depthwise convolution of ``kda_conv`` taps each; the decay and the
    #: output gate through low-rank pairs ``kda_head_dim`` wide; the walk
    #: taken ``kda_chunk`` positions at a time (a size the configuration
    #: states, as ``ssm_chunk``; how many chunks a turn of the walk takes
    #: and the rows of a sub-block are set from the chip's timings and are
    #: ops/kda.py's ``CHUNK_GROUP`` and ``SUB_ROWS``).  No positional
    #: encoding.
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv: int = 0
    kda_chunk: int = 0
    #: ``layer_layout`` 6, a gated delta rule (ops/kda.py with ONE decay
    #: a head): ``gdn_heads`` heads, each carrying a ``(gdn_key_dim,
    #: gdn_value_dim)`` float32 state that every position decays by one
    #: factor and corrects by a rank-one step ``b`` times the miss, ``b =
    #: gdn_beta_scale * sigmoid`` (2 where the model lets the correction
    #: overshoot: the state's transition then has eigenvalues down to
    #: -1); queries and keys ``gdn_key_dim`` wide and values
    #: ``gdn_value_dim`` behind causal depthwise convolutions of
    #: ``gdn_conv`` taps; decay and step straight from the stream, a head;
    #: a full-rank output gate under ``silu``; the walk taken
    #: ``gdn_chunk`` positions at a time.  No positional encoding.  Such
    #: a layer shares a model with kinds 0..3.
    gdn_heads: int = 0
    gdn_key_dim: int = 0
    gdn_value_dim: int = 0
    gdn_conv: int = 0
    gdn_chunk: int = 0
    gdn_beta_scale: float = 1.0
    #: Where a block's two norms sit: False, on each sublayer's input
    #: (``x + f(RMSNorm(x))``, every other configuration's); True, on its
    #: output (``x + RMSNorm(f(x))``: mixer and feed-forward read the
    #: stream as it is).  A plain residual's (``hc_streams`` 1).
    post_norm: bool = False
    #: A layer of kind 0 or 1 takes an RMSNorm over the WHOLE width of its
    #: query projection and of its key projection (``n_heads * head_dim``,
    #: ``n_kv_heads * head_dim``: one mean of squares a position, a scale
    #: a channel) before the heads are split (kind 2 has one a head).
    qk_norm_whole: bool = False
    #: ``moe_experts == 0``: every layer's feed-forward is one dense gated
    #: MLP ``hidden -> ffn_size -> hidden`` (``hidden_act`` on the gate),
    #: with no router.
    ffn_size: int = 0
    #: The head is the embedding, transposed: one leaf, whose gradient
    #: comes from both uses.
    tie_embeddings: bool = False
    #: Scalars a model's config states, each at the value that leaves the
    #: layer as the other decoder configurations compute it: the embedding
    #: rows are multiplied by ``embedding_multiplier``, each block's
    #: mixer and feed-forward output by ``residual_multiplier`` before it
    #: joins the stream, the attention scores by ``attention_multiplier``
    #: (None: ``1 / sqrt(head_dim)``), and the logits are divided by
    #: ``logits_scaling``.
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None
    logits_scaling: float = 1.0
    #: ``layer_layout`` 4, latent attention (models/decoder.py): queries
    #: through a ``q_lora_rank``-wide normalised latent (0: one direct
    #: product, no latent and no norm), keys and values
    #: through a ``kv_lora_rank``-wide one; a head's query and key are
    #: ``qk_nope_head_dim`` wide without position plus ``qk_rope_head_dim``
    #: rotary dims whose key is ONE head shared by all ``n_heads``; values
    #: are ``v_head_dim`` wide.  ``head_dim`` / ``n_kv_heads`` are not
    #: read by such a layer.  ``mla_use_nope``: the model states no
    #: position in these layers: the ``qk_rope_head_dim`` dims of the
    #: query and the one shared key stay, unrotated (``rope_theta`` and
    #: the ``rope_*`` group below are then not read).
    mla_use_nope: bool = False
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    #: YaRN's stretch of that layer's rotary frequencies (``rope_factor``
    #: 1: plain rotary at ``rope_theta``): dims that turn more than
    #: ``rope_beta_fast`` times over ``rope_original_max`` positions keep
    #: their frequency, those under ``rope_beta_slow`` turns are slowed
    #: by the factor, a ramp between; the scores are multiplied by
    #: ``(0.1 ln(rope_factor) + 1)^2`` beside ``1 / sqrt(q width)``.
    rope_factor: float = 1.0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_original_max: int = 0
    #: With ``moe_experts > 0``: the first ``first_dense_layers`` layers'
    #: feed-forward is the dense MLP of ``ffn_size`` instead of experts.
    first_dense_layers: int = 0
    #: Experts every token passes through beside its routed ones (one
    #: gated MLP ``moe_shared_experts * moe_ffn_size`` wide, no gate).
    moe_shared_experts: int = 0
    #: The router's scores: "softmax" over all experts, or "sigmoid" of
    #: each (ops/moe.py ``route``): the top-k then chosen on score +
    #: a selection bias, gates from the unbiased scores, normalised over
    #: the top-k, times ``moe_routed_scaling``.
    moe_scoring: str = "softmax"
    moe_routed_scaling: float = 1.0
    #: > 0: the router carries a selection bias (E,), a parameter no
    #: gradient reaches and the optimizer does not move; after each train
    #: step ``bias_e += moe_bias_rate * sign(mean load - load_e)`` over
    #: the step's pairs on all ``moe_experts`` (train/tasks.py).
    moe_bias_rate: float = 0.0
    #: > 0: each expert layer adds a balance term to what training
    #: differentiates, a sequence at a time (models/decoder.py, "The
    #: declared loss term"): ``alpha * sum_e f_e P_e`` over all
    #: ``moe_experts``, ``f_e`` the share of the sequence's pairs expert
    #: ``e`` was chosen for (times ``E / K``), ``P_e`` its mean normalised
    #: score.  0: no term, and no operation of the program changes.
    moe_seq_aux_alpha: float = 0.0
    #: Lanes of the residual stream (ops/hyper_connection.py): 1 is the
    #: plain residual; ``n > 1`` carries ``(B, T, n, hidden)`` and wraps
    #: each sublayer in learned pre / post / residual mixing, the
    #: residual mix made doubly stochastic by ``hc_sinkhorn_iters`` turns
    #: of Sinkhorn's iteration (``hc_eps`` beside each sum) from logits
    #: clipped to +-``hc_res_clamp``.  A fresh block reads and writes
    #: lane 0 and remixes by nearly the identity (models/decoder.py
    #: ``HC_OFFSET_INIT``).
    hc_streams: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: float = 30.0


@dataclass(frozen=True)
class TrainConfig:
    """Training-harness hyperparameters (ref: notebook cells 11/29)."""

    batch_size: int = 2
    window: int = 30
    chunk_size: int = 100
    learning_rate: float = 1e-3
    epochs: int = 25
    clip: float = 50.0
    val_size: float = 0.1
    test_size: float = 0.1
    fbeta_beta: float = 0.5
    prob_threshold: float = 0.5
    seed: int = 0
    checkpoint_dir: str = "checkpoints"
    #: Microbatch gradient-accumulation factor K.  The batch is split
    #: into K microbatches scanned into one donated optimizer update —
    #: the same algebra as the full batch (per-microbatch loss *sums*
    #: and mask counts are accumulated and normalized once at the end),
    #: equal up to float32 re-association (docs/training.md).  Must
    #: divide ``batch_size``.  1 = the seed step, bit-identical.
    accum_steps: int = 1
    #: Input-pipeline prefetch depth: how many composed+transferred
    #: batches may be in flight ahead of the device step.  Host window
    #: gather/normalization of chunk k+1 overlaps device compute of
    #: chunk k behind a bounded queue; stalls surface as the
    #: ``train_input_stall_seconds`` histogram.  1 still overlaps by a
    #: single batch; 0 disables the background thread (synchronous).
    prefetch_depth: int = 2
    #: Per-chunk normalized-window cache capacity in chunks (LRU).
    #: Epochs >= 2 reuse the gathered windows instead of re-fetching,
    #: re-normalizing and re-gathering every pass.  Host RAM bound is
    #: ``cache_chunks * chunk_size * window * n_features * 4`` bytes.
    #: 0 disables caching (the seed behavior).
    cache_chunks: int = 64
    #: Continuous fine-tuning (``ContinuousTrainer``): fresh rows that
    #: must land in the warehouse before a fine-tune round fires.
    continuous_min_rows: int = 256
    #: Sliding history window (rows) each round trains over.
    continuous_window_rows: int = 2048
    #: Epochs per fine-tune round (warm-started from the last round).
    continuous_epochs: int = 1
    #: Consecutive empty tail polls before the follow reader concludes
    #: the warehouse has quiesced and the loop drains and exits.
    continuous_follow_polls: int = 8
    #: Wall seconds between empty tail polls (tests inject a waiter
    #: instead — no wall sleeps in tier-1).
    continuous_poll_s: float = 1.0

    def __post_init__(self) -> None:
        if self.accum_steps < 1:
            raise ValueError(
                f"train.accum_steps must be >= 1, got {self.accum_steps}")
        if self.batch_size % self.accum_steps != 0:
            raise ValueError(
                f"train.accum_steps ({self.accum_steps}) must divide "
                f"train.batch_size ({self.batch_size}): microbatches are "
                f"equal fixed-shape slices")
        if self.prefetch_depth < 0 or self.cache_chunks < 0:
            raise ValueError(
                f"train.prefetch_depth/cache_chunks must be >= 0, got "
                f"{self.prefetch_depth}/{self.cache_chunks}")
        if (self.continuous_min_rows < 1 or self.continuous_window_rows < 1
                or self.continuous_epochs < 1
                or self.continuous_follow_polls < 1):
            raise ValueError(
                "train.continuous_min_rows/continuous_window_rows/"
                "continuous_epochs/continuous_follow_polls must be >= 1")
        if self.continuous_poll_s <= 0:
            raise ValueError(
                f"train.continuous_poll_s must be > 0, got "
                f"{self.continuous_poll_s}")


@dataclass(frozen=True)
class MeshConfig:
    """Device-mesh layout for pjit/shard_map parallelism (net-new vs the
    single-machine reference; SURVEY.md §2 parallelism inventory)."""

    #: Data-parallel axis size; -1 means "all remaining devices".
    dp: int = -1
    #: Sequence-parallel axis size (long-context recurrent scan sharding).
    sp: int = 1
    #: Expected process (host/slice) count.  >1 = multi-host: the mesh
    #: spans every process's devices with dp crossing the host boundary
    #: (gradient all-reduce rides DCN between slices, ICI within) and sp
    #: kept inside one host.  Validated against jax.process_count() at
    #: mesh build so a mis-launched job fails loudly, not wrongly.
    processes: int = 1
    dp_axis: str = "dp"
    sp_axis: str = "sp"


@dataclass(frozen=True)
class EngineConfig:
    """Streaming-engine runtime knobs (the role Spark's runtime config
    plays for the reference's consumer)."""

    #: "python" or "native" — the C++ interval-join scheduler
    #: (native/joincore.cpp); falls back to the (bit-identical) python
    #: path with a warning if the toolchain is absent.
    join_backend: str = "python"
    #: Durable-state write cadence in steps (1 = every step; N amortises
    #: over replay churn, idempotent re-landing covers the crash window).
    checkpoint_every: int = 1
    #: Engine state file (offsets + in-flight join state); None disables.
    checkpoint_path: Optional[str] = None
    #: Degraded-mode join deadline (stream-time seconds): a side stream
    #: whose watermark trails the newest book tick by more than this
    #: stops blocking the join — rows emit with the stream's last-known
    #: (or absent) values, counted per topic, and the ``feed_degraded``
    #: health check flips until the feed recovers.  None keeps the
    #: strict inner-join stall.  Keep it below
    #: ``watermark_s + 2*join_tolerance_s`` (660 s at the default
    #: feature config) or waiting ticks can lose their healthy matches
    #: to watermark eviction (a counted drop) before the ghost arrives.
    staleness_deadline_s: Optional[int] = None


#: Fleet-runtime defaults shared by RuntimeConfig and the direct
#: constructors (BatcherConfig, FleetGateway) so direct constructions
#: (tests, the benchmark's drivers) can't drift from the config defaults.
DEFAULT_BUCKET_SIZES: Tuple[int, ...] = (8, 32, 64, 128)
DEFAULT_MAX_LINGER_S: float = 0.002
DEFAULT_QUEUE_BOUND: int = 1024


@dataclass(frozen=True)
class RuntimeConfig:
    """Fleet-serving runtime knobs (fmda_tpu.runtime; docs/runtime.md).

    Net-new vs the reference (its serving is one hand-run predict.py per
    process) — these size the multi-tenant gateway → micro-batcher →
    session-pool path.
    """

    #: Max concurrent sessions (slots in the pooled state tree).
    capacity: int = 128
    #: Ascending padded micro-batch sizes; each is ONE compiled XLA
    #: program, replayed forever (keep the set small).  64 is in the
    #: default set because it is the documented default fleet size —
    #: without it a 64-session flush pads to 128 and half the batched
    #: step is wasted lanes.
    bucket_sizes: Tuple[int, ...] = DEFAULT_BUCKET_SIZES
    #: Max time (ms) the oldest queued tick may linger before a flush is
    #: forced — the latency half of the batching trade.
    max_linger_ms: float = DEFAULT_MAX_LINGER_S * 1e3
    #: Bound on queued ticks; overload sheds the oldest, counted.
    queue_bound: int = DEFAULT_QUEUE_BOUND
    #: Pooled-head trailing window of the carried streaming state.
    window: int = 30
    #: Flush pipelining in the gateway: 1 = one-deep overlap (flush k's
    #: host transfer + publish run while flush k+1 dispatches — the
    #: default hot path), 0 = strictly serial flushes (the A/B reference;
    #: results are bit-identical either way, tests assert it).
    pipeline_depth: int = 1
    #: Shard the slot axis of the pool's state tree across the dp axis of
    #: the device mesh (config.mesh) so fleet capacity scales with chip
    #: count.  Off by default: on one device the unsharded path is taken
    #: regardless (bit-identical), and multi-chip serving is an explicit
    #: deployment decision.
    shard_pool: bool = False
    #: Latency-SLO gate for `serve-fleet`: p99 of the submit→publish
    #: ("total") histogram must stay under this bound (ms), else the
    #: command exits 1 (tests/test_runtime.py).  None disables the
    #: gate; `--slo-soft` reports the verdict without failing.
    slo_p99_ms: Optional[float] = None

    # -- the batched Predictor path (window-re-scan serving on the fleet
    # runtime: fmda_tpu.runtime.predictor_pool; docs/runtime.md) --------

    #: Padded micro-batch sizes for the batched Predictor's jitted
    #: (B, window, F) forward — one compiled program each.  Smaller set
    #: than the carried-state fleet's: each window forward is
    #: O(window·F) device work, so padding waste is costlier.
    predictor_bucket_sizes: Tuple[int, ...] = (8, 32, 64)
    #: Max time (ms) the oldest queued signal may linger before a flush.
    predictor_max_linger_ms: float = DEFAULT_MAX_LINGER_S * 1e3
    #: Bound on queued signals; overload sheds the oldest, counted.
    predictor_queue_bound: int = DEFAULT_QUEUE_BOUND
    #: Model input window for the batched Predictor; None = `window`.
    predictor_window: Optional[int] = None
    #: Keep a device-resident ring of the stream's newest `window`
    #: feature rows: consecutive signals re-send only the new rows and
    #: the (B, window, F) gather happens on device.  Off by default —
    #: it assumes in-order landing (an out-of-order row's derived-view
    #: recompute would not reach rows already on device).
    predictor_ring: bool = False


@dataclass(frozen=True)
class FleetTopologyConfig:
    """Multi-host serving topology knobs (fmda_tpu.fleet;
    docs/multihost.md).

    Net-new vs the reference and vs the single-process fleet runtime:
    N worker processes each own a contiguous slot-range of the session
    hash space (each embedding the PR-1 FleetGateway/SessionPool), a
    router hashes session → owner and drives membership + migration over
    the cross-process bus (a BusServer-served NativeBus locally, Kafka
    in prod).
    """

    #: Worker-process count the local launcher spawns (`serve-fleet
    #: --role local`); membership itself is dynamic — workers may join
    #: and leave a running router at any time.
    n_workers: int = 2
    #: Worker ids are ``<worker_prefix><index>`` (w0, w1, ...) for the
    #: launcher; hand-started workers may use any id.
    worker_prefix: str = "w"
    #: Bus-server bind address for the local cross-process transport
    #: (the router hosts the bus; workers connect with SocketBus).
    host: str = "127.0.0.1"
    #: 0 = ephemeral (the launcher reads the bound port off the server).
    port: int = 0
    #: Worker heartbeat cadence on the control topic.
    heartbeat_interval_s: float = 0.5
    #: Router declares a worker dead after this long without a
    #: heartbeat (measured on the router's own clock at receipt, so
    #: cross-process clock skew cannot mis-kill a healthy worker).
    #: Deliberately ~20x the interval: a worker mid-drain under a deep
    #: backlog beats late, and a false death costs carried state.
    heartbeat_timeout_s: float = 10.0
    #: Size of the session hash space the ownership table partitions
    #: into contiguous per-worker ranges.
    hash_space: int = 1 << 16
    #: Bound on ticks the router buffers per migrating session while its
    #: state is in flight between owners; overflow sheds the oldest,
    #: counted (``migration_buffer_shed``) — same never-silent contract
    #: as the gateway queue.
    migration_buffer_bound: int = 4096
    #: Max inbox records a worker consumes per step (bounds one socket
    #: read's frame size; the backlog simply spans more steps).
    worker_poll_max_records: int = 512
    #: Router backpressure bound: once this many routed ticks are
    #: unanswered, ``saturated`` turns on and well-behaved producers
    #: pace themselves — otherwise an unbounded inbox backlog outruns
    #: the bus's retention and ticks silently age off the topic.
    max_inflight_ticks: int = 4096
    #: Age (router clock) after which an unanswered tick is declared
    #: lost (``results_missing``) — e.g. it rode into a worker that
    #: died undrained.
    result_timeout_s: float = 60.0
    #: Byte arena per topic for the router-hosted NativeBus — sized for
    #: deep tick backlogs (a ~700B tick message × max_inflight_ticks ×
    #: workers fits with wide margin).
    bus_arena_bytes: int = 1 << 26
    #: How long a shared-bus worker retries a dead broker before exiting
    #: cleanly (counted, rc 0 — the never-abort contract).  A
    #: worker-hosted-bus worker never exits on control loss: its data
    #: plane is local, so it keeps serving and re-dials instead.
    bus_error_grace_s: float = 10.0
    #: Control-plane re-dial cadence while the router/broker is
    #: unreachable (split topology; reconnect re-hellos with the session
    #: report, which is how a restarted router adopts the sessions).
    control_retry_s: float = 1.0
    #: Frame encoding on every SocketBus link (docs/multihost.md "Wire
    #: format v2"): ``auto`` negotiates the binary codec at connect and
    #: falls back to JSON against a peer that does not speak it (mixed-
    #: version fleets interoperate); ``binary`` insists (still falls
    #: back, loudly); ``json`` pins the pre-v2 text frames — the
    #: rollback switch.
    wire_format: str = "auto"


@dataclass(frozen=True)
class ObservabilityConfig:
    """Observability-plane knobs (fmda_tpu.obs; docs/observability.md).

    Net-new vs the reference (its only "telemetry" is print statements):
    one process-wide metrics registry + JSONL event ring, with an
    optional Prometheus scrape endpoint.
    """

    #: Switch for the app's plane: False hands out no-op instruments to
    #: the engine/bus/warehouse, registers no collectors, and starts no
    #: endpoint — those hot paths keep only one attribute call.
    #: Module-level instrumentation with no Application handle (ingest
    #: transports, trainer step timings) reports to the process-default
    #: registry regardless; its cost is one lock-guarded update per
    #: event (not measured on the chip machine's host).
    enabled: bool = True
    #: Serve ``/metrics``+``/healthz``+``/snapshot`` over HTTP.  Off by
    #: default so tests and one-shot CLI runs never bind a port; daemons
    #: opt in (or pass ``serve-fleet --metrics-port``).
    endpoint_enabled: bool = False
    host: str = "127.0.0.1"
    #: 0 = ephemeral (the bound port is logged and on the handle).
    port: int = 9100
    #: Bounded event-ring capacity (oldest events fall off).
    events_capacity: int = 2048
    #: Mirror events to this JSONL file; None = ring only.
    events_path: Optional[str] = None
    #: ``/healthz`` turns degraded when the newest completed app tick is
    #: older than this (startup grace: healthy until the first tick).
    max_tick_age_s: float = 900.0


@dataclass(frozen=True)
class SLOConfig:
    """Fleet service-level objectives + telemetry knobs (fmda_tpu.obs:
    tsdb/aggregate/slo/recorder; docs/observability.md "Fleet
    aggregation, SLOs, and the flight recorder").

    Declarative objectives evaluated as **multi-window burn rates**: an
    alert fires when both the fast (~5 m) and slow (~1 h) windows burn
    error budget faster than ``burn_threshold``, and clears as soon as
    the fast window recovers.  Evaluation is pull-based — one fold of
    heartbeat stats + scrape snapshots per ``interval_s``, never on the
    tick hot path.
    """

    #: Master switch for router-side fleet telemetry (the store, the
    #: aggregator, SLO evaluation, and the flight recorder).
    enabled: bool = True
    #: Time-series sample grid + SLO evaluation cadence (seconds).
    interval_s: float = 5.0
    #: History the store retains per series (ring capacity =
    #: retention_s / interval_s bins).
    retention_s: float = 7200.0
    #: Cadence for scraping worker ``/snapshot`` endpoints (announced
    #: in heartbeats); heartbeat stats fold in every ``interval_s``.
    scrape_interval_s: float = 10.0
    #: Burn-rate windows (seconds): fast trips quickly on a cliff,
    #: slow keeps a brief blip from paging.
    fast_window_s: float = 300.0
    slow_window_s: float = 3600.0
    #: Burn rate (budget consumption multiple) at which an alert fires.
    burn_threshold: float = 2.0
    #: Latency objective: at most ``latency_budget`` of served ticks may
    #: exceed ``latency_p99_ms`` end to end.  None disables.
    latency_p99_ms: Optional[float] = 250.0
    latency_budget: float = 0.05
    #: Loss objective: counted losses / (served + lost) stays under this.
    loss_budget: float = 0.001
    #: Journal objective: warehouse journal backlog above this depth is
    #: budget burn (``journal_budget`` of samples may exceed it).
    journal_depth: int = 1024
    journal_budget: float = 0.1
    #: Degraded-feed objective: minutes per slow window any side feed
    #: may serve ghost rows before the alert fires.
    degraded_feed_budget_minutes: float = 5.0
    #: Recompile objective: unexpected XLA recompiles after warmup are
    #: judged as a raw count per window — a budget below 1 means a
    #: single recompile burns past ``burn_threshold`` (zero is the
    #: steady-state contract; fmda_tpu.obs.device).
    recompile_budget: float = 0.5
    #: Memory-leak objective: fraction of samples the device memory
    #: monitor's monotonic-growth heuristic may be raised.
    memory_leak_budget: float = 0.05
    #: Quality objectives (fmda_tpu.obs.quality's label-join evaluator
    #: writes the series; None-until-reported — a fleet without the
    #: quality plane never fires these).  Accuracy: exact-match misses
    #: over joined predictions stay under this fraction.
    quality_accuracy_budget: float = 0.35
    #: Per-label F-beta floor: fraction of sampled intervals where ANY
    #: (version, label) F-beta gauge sits below ``quality_fbeta_floor``.
    quality_fbeta_floor: float = 0.05
    quality_fbeta_budget: float = 0.25
    #: Drift: fraction of sampled intervals where the worst PSI
    #: (feature or prediction) exceeds ``quality_drift_psi`` (0.25 is
    #: the classic "action required" PSI threshold).
    quality_drift_psi: float = 0.25
    quality_drift_budget: float = 0.1
    #: Flight-recorder bundle directory; None disables postmortems.
    postmortem_dir: Optional[str] = None
    #: Rotated bundle count (oldest deleted past this).
    postmortem_keep: int = 4
    #: Debounce between bundles for one trigger reason (seconds).
    postmortem_min_interval_s: float = 60.0


@dataclass(frozen=True)
class QualityConfig:
    """Online model-quality plane knobs (fmda_tpu.obs.quality;
    docs/observability.md "Model quality").

    The label-join evaluator captures published predictions into a
    bounded ring and joins them — on a cadence, off the tick path —
    against warehouse targets once enough future rows have landed
    (``FeatureConfig.max_lead`` rows after a prediction's own row).
    Streaming subset-accuracy / Hamming / per-label F-beta accumulate
    per ``weights_version``; a PSI drift monitor scores live features
    and predictions against the training-time reference profile saved
    beside the checkpoint (``quality_profile.json``).
    """

    #: Master switch for the quality plane (capture + join + drift).
    enabled: bool = True
    #: Capture-ring capacity; overflow evicts the oldest prediction as
    #: a counted ``quality_captures_shed`` loss, never unbounded.
    capture_capacity: int = 4096
    #: Label-join cadence (seconds; virtual seconds under replay).
    join_interval_s: float = 5.0
    #: Probability threshold for label decisions (predictions arrive as
    #: probabilities — sigmoid already applied by the serving pool).
    prob_threshold: float = 0.5
    #: F-beta beta (0.5 = precision-weighted, the trainer's choice).
    fbeta: float = 0.5
    #: A capture still unjoinable after this many consecutive join
    #: rounds (row shed, session gone, beyond retention) ages out as a
    #: counted ``quality_join_expired`` loss — round-counted, so replay
    #: runs expire deterministically with no wall clock involved.
    max_join_attempts: int = 8
    #: Reference-profile quantile bins (built at train time).
    drift_bins: int = 10
    #: Drift scores stay None (never reported) below this many observed
    #: rows — PSI over a handful of rows is noise, not signal.
    drift_min_samples: int = 64
    #: Reference-profile path; None = ``quality_profile.json`` beside
    #: the checkpoint in use.
    profile_path: Optional[str] = None
    #: Hot-swap guardrail (fmda_tpu.eval.shadow): a candidate may score
    #: at most this much *below* the incumbent's shadow accuracy.
    swap_margin: float = 0.02
    #: Shadow-scoring replay size: rounds x sessions of recent
    #: warehoused history per side.
    swap_eval_rounds: int = 48
    swap_eval_sessions: int = 4

    def __post_init__(self) -> None:
        if self.capture_capacity < 1:
            raise ValueError(
                f"capture_capacity must be >= 1, got {self.capture_capacity}")
        if self.join_interval_s <= 0:
            raise ValueError(
                f"join_interval_s must be > 0, got {self.join_interval_s}")
        if not 0.0 < self.prob_threshold < 1.0:
            raise ValueError(
                f"prob_threshold must be in (0, 1), got "
                f"{self.prob_threshold}")
        if self.max_join_attempts < 1:
            raise ValueError(
                f"max_join_attempts must be >= 1, got "
                f"{self.max_join_attempts}")
        if self.drift_bins < 2:
            raise ValueError(
                f"drift_bins must be >= 2, got {self.drift_bins}")
        if self.swap_margin < 0:
            raise ValueError(
                f"swap_margin must be >= 0, got {self.swap_margin}")
        if self.swap_eval_rounds < 1 or self.swap_eval_sessions < 1:
            raise ValueError(
                "swap_eval_rounds and swap_eval_sessions must be >= 1, "
                f"got {self.swap_eval_rounds} x {self.swap_eval_sessions}")


@dataclass(frozen=True)
class TracingConfig:
    """End-to-end tick tracing knobs (fmda_tpu.obs.trace;
    docs/observability.md "Tracing a tick").

    Off by default: disabled tracing costs one branch on every hot path
    (submit, flush, bus publish, engine step).  Enabled tracing records
    spans into a bounded in-memory ring, exported as Chrome/Perfetto
    trace_event JSON (``/trace``, ``python -m fmda_tpu trace``,
    ``serve-fleet --trace-out``).
    """

    #: Master switch for the process tracer.
    enabled: bool = False
    #: Fraction of trace roots sampled in [0, 1].  1.0 traces every tick
    #: (forensics runs); production fleets run ~0.01, where all but one
    #: tick in a hundred take the disabled path's no-op singletons
    #: (tests/test_trace.py; the cost on the chip's host: not measured).
    sample_rate: float = 1.0
    #: Span-ring capacity; overflow evicts the oldest spans, so a
    #: long-running daemon keeps the newest traces and bounded memory.
    max_spans: int = 16384


@dataclass(frozen=True)
class ProfilingConfig:
    """Device & compiler observability knobs (fmda_tpu.obs.device /
    fmda_tpu.obs.pyprof; docs/observability.md "Device & compiler
    telemetry").

    The compile ledger itself is on by default everywhere — a tracked
    jit call with the ledger enabled costs two cache-size reads, one
    short lock window and one thread-local set and reset (part of
    ``train_dispatch_us`` in the training cells, ``PERF.md`` §5).
    ``cost_analysis`` asks each program once per compile for its
    FLOPs/bytes and its memory, from the call's own kept signature, which
    finds jax's cached lowering and executable (no second compile since
    PR 51); it stays a *deployment* default (serving hosts want MFU —
    the module-level default is off and ``configure_device_obs``
    applies this section at serve time).
    """

    #: Master switch for the ledger + memory monitor.
    enabled: bool = True
    #: Probe ``.lower().compile().cost_analysis()`` per compile (via
    #: fmda_tpu.compat) for per-program FLOPs / bytes-accessed → MFU.
    cost_analysis: bool = True
    #: Run the continuous host sampling profiler (``/profile``,
    #: flight-recorder ``profile.folded``).
    host_profiler: bool = False
    #: Host-profiler sampling period (milliseconds).
    profile_interval_ms: float = 10.0
    #: Bounded distinct-stack table; overflow folds into ``<other>``.
    profile_max_stacks: int = 4096
    #: Device memory sampling cadence (seconds).
    memory_interval_s: float = 5.0
    #: Consecutive strictly-growing samples before the leak heuristic
    #: raises ``device_memory_leak_suspected``.
    memory_leak_window: int = 12


@dataclass(frozen=True)
class ChaosConfig:
    """Fault-injection knobs (fmda_tpu.chaos; docs/chaos.md).

    Off by default: with ``enabled=False`` nothing is injected and every
    compiled-in injection point costs exactly one branch (the tier-1 AST
    check pins this).  The rate knobs parameterise
    :meth:`~fmda_tpu.chaos.plan.FaultPlan.generate` when no explicit
    ``--chaos-plan`` file is given — the plan is a pure function of
    ``seed`` and these counts, so a run is its own reproduction recipe.
    """

    #: Master switch for the process chaos runtime.
    enabled: bool = False
    #: Seed the generated fault plan derives from.
    seed: int = 0
    #: Worker processes killed (and revived ``revive_after`` steps
    #: later) per soak.
    worker_kills: int = 1
    #: Virtual steps a killed worker stays down before its replacement
    #: spawns.
    revive_after: int = 8
    #: Router kill/takeover events per soak (each exercises the
    #: registry-rebuild failover path).
    router_restarts: int = 1
    #: Router→worker data-link partition windows per soak.
    link_partitions: int = 1
    #: Control-bus outage windows per soak (the router keeps pumping its
    #: links while its own bus is down — counted, never fatal).
    bus_blips: int = 1
    #: Injected per-op delay events per soak.
    delays: int = 2
    #: Sleep per delayed op (seconds).
    delay_s: float = 0.02
    #: Fault-free steps at both ends of the schedule: a clean warm-up,
    #: and the post-chaos window the "ticks served after the last
    #: fault" gate measures in.
    settle_steps: int = 5

    # -- data-plane soak knobs (fmda_tpu.chaos.pipeline; the fleet soak
    # above ignores these) ---------------------------------------------

    #: Side-feed outage windows per pipeline soak (degraded-mode joins).
    feed_outages: int = 1
    #: Virtual steps a feed stays down.
    feed_outage_steps: int = 8
    #: Warehouse-unreachable windows per pipeline soak (journal spill).
    warehouse_outages: int = 1
    #: Virtual steps the warehouse stays down.
    warehouse_outage_steps: int = 4
    #: Engine kill/restore cycles per pipeline soak.
    engine_kills: int = 1
    #: Virtual steps the engine stays dead before its restore.
    engine_kill_steps: int = 2


@dataclass(frozen=True)
class ControlConfig:
    """Adaptive control plane knobs (fmda_tpu.control; docs/control.md).

    Three closed loops run beside the router, all reading the telemetry
    plane (``[slo]``'s windowed p99 / burn rates) and writing decisions
    to the EventLog: the **batching controller** (tunes gateway linger
    and bucket cap against the latency objective), **per-tenant QoS**
    (weighted admission + counted per-class shedding in front of the
    gateway queue), and the **elastic autoscaler** (spawns workers on
    sustained burn, retires them through the zero-loss drain/export/
    replay migration on sustained idle).  ``enabled=False`` removes
    every loop: the serving path is exactly the static fleet.
    """

    #: Master switch for the control plane (``serve-fleet
    #: --no-controller`` overrides per run for A/B).
    enabled: bool = True
    #: Decision cadence (seconds between control evaluations).
    interval_s: float = 1.0
    #: Last-N decision ring surfaced by ``/control`` and ``status``.
    decisions_keep: int = 64

    # -- batching controller --------------------------------------------
    #: Enable the linger/bucket feedback loop.
    batching: bool = True
    #: p99 target (ms) the loop steers toward; None derives it from
    #: ``slo.latency_p99_ms``.
    target_p99_ms: Optional[float] = None
    #: Hysteresis deadband as a fraction of target: no move while p99
    #: sits inside [(1-h)·target, (1+h)·target].
    hysteresis: float = 0.25
    #: Bounded step per decision (ms of linger) — the loop never jumps.
    linger_step_ms: float = 0.25
    #: Linger clamp (ms).  The controller explores inside these walls.
    min_linger_ms: float = 0.0
    max_linger_ms: float = 8.0

    # -- per-tenant QoS -------------------------------------------------
    #: Priority classes, highest first.  Parallel tuples: ``weights``
    #: set each class's fair share of the gateway queue (WFQ), and
    #: ``quota_frac`` caps each class's queued ticks at that fraction
    #: of ``runtime.queue_bound`` (over-quota submits shed the class's
    #: OWN oldest tick, counted ``quota_shed``).  Empty = QoS off
    #: (global oldest-drop, exactly the pre-control gateway).
    tenant_classes: Tuple[str, ...] = ()
    tenant_weights: Tuple[float, ...] = ()
    tenant_quota_frac: Tuple[float, ...] = ()
    #: Class assigned to sessions opened without a tenant label.
    default_class: str = "standard"

    # -- elastic autoscaler ---------------------------------------------
    #: Enable the worker-count loop (needs a spawn-capable actuator —
    #: the local launcher topology; a bare router run leaves it off).
    autoscale: bool = True
    min_workers: int = 1
    max_workers: int = 8
    #: Scale up when the latency objective's fast burn rate holds at or
    #: above this for ``up_sustain_s`` seconds.
    scale_up_burn: float = 1.0
    up_sustain_s: float = 3.0
    #: Scale down when p99 holds below ``scale_down_frac``·target (and
    #: no burn) for ``down_sustain_s`` seconds.
    scale_down_frac: float = 0.3
    down_sustain_s: float = 10.0
    #: Minimum seconds between scaling moves (either direction).
    cooldown_s: float = 5.0

    def __post_init__(self) -> None:
        n = len(self.tenant_classes)
        if len(self.tenant_weights) != n or len(self.tenant_quota_frac) != n:
            raise ValueError(
                "tenant_classes/tenant_weights/tenant_quota_frac must be "
                f"parallel tuples, got lengths {n}/"
                f"{len(self.tenant_weights)}/{len(self.tenant_quota_frac)}")
        if any(w <= 0 for w in self.tenant_weights):
            raise ValueError("tenant_weights must be positive")
        if self.min_workers < 1 or self.max_workers < self.min_workers:
            raise ValueError(
                f"need 1 <= min_workers <= max_workers, got "
                f"{self.min_workers}/{self.max_workers}")
        if self.min_linger_ms < 0 or self.max_linger_ms < self.min_linger_ms:
            raise ValueError(
                f"need 0 <= min_linger_ms <= max_linger_ms, got "
                f"{self.min_linger_ms}/{self.max_linger_ms}")


@dataclass(frozen=True)
class ReplayConfig:
    """Historical-replay knobs (fmda_tpu.replay; docs/replay.md).

    A replay run backfills history through the **unmodified** serving
    path at max speed on a virtual clock (the rows' own timestamps —
    never the host clock; the ``virtual-clock`` lint rule pins that).
    These knobs pick the history source and bound the run; the serving
    side needs nothing — replay sessions are ordinary gateway sessions.
    """

    #: History source: ``"synthetic"`` (seeded generator — bit-identical
    #: re-iteration, no warehouse needed) or ``"warehouse"`` (bulk
    #: chunked reads via ``Warehouse.iter_row_chunks``).
    source: str = "synthetic"
    #: Tickers (= replay sessions) the backfill drives.
    n_tickers: int = 8
    #: Rounds served when ``source="synthetic"``.
    n_rounds: int = 256
    #: Seed for the synthetic generator and tenant assignment.
    seed: int = 0
    #: Fraction of tickers active per synthetic round (1.0 = lockstep,
    #: the composition the bit-identity gate requires).
    duty: float = 1.0
    #: Virtual seconds between synthetic rounds (the virtual clock's
    #: step; also the implied live cadence replay deletes).
    step_s: float = 60.0
    #: Warehouse row-range bounds (timestamp strings; None = unbounded)
    #: when ``source="warehouse"``.
    start_ts: Optional[str] = None
    end_ts: Optional[str] = None
    #: Rows per keyset-paginated warehouse read.
    chunk: int = 4096
    #: Wire dialect blocks round-trip through before serving: None
    #: (in-process), ``"binary"`` or ``"json"`` — identity must hold on
    #: all three (solo gateways only; a fleet router encodes per link).
    wire_dialect: Optional[str] = None

    def __post_init__(self) -> None:
        if self.source not in ("synthetic", "warehouse"):
            raise ValueError(
                f"replay.source must be 'synthetic' or 'warehouse', "
                f"got {self.source!r}")
        if self.wire_dialect not in (None, "binary", "json"):
            raise ValueError(
                f"replay.wire_dialect must be null, 'binary' or 'json', "
                f"got {self.wire_dialect!r}")
        if self.n_tickers < 1 or self.n_rounds < 1 or self.chunk < 1:
            raise ValueError(
                f"replay.n_tickers/n_rounds/chunk must be >= 1, got "
                f"{self.n_tickers}/{self.n_rounds}/{self.chunk}")
        if not 0.0 < self.duty <= 1.0:
            raise ValueError(
                f"replay.duty must be in (0, 1], got {self.duty}")


@dataclass(frozen=True)
class SessionConfig:
    """Ingestion-session driver knobs (ref: producer.py:257-263)."""

    freq_s: int = 300
    source: str = "IEX"
    symbol: str = "spy"
    countries: Tuple[str, ...] = ("United States",)
    importance: Tuple[str, ...] = ("1", "2", "3")
    cot_subject: str = "S&P 500 STOCK INDEX"
    timezone: str = "US/Eastern"


@dataclass(frozen=True)
class FrameworkConfig:
    """Top-level aggregate configuration."""

    features: FeatureConfig = field(default_factory=FeatureConfig)
    bus: BusConfig = field(default_factory=BusConfig)
    warehouse: WarehouseConfig = field(default_factory=WarehouseConfig)
    engine: EngineConfig = field(default_factory=EngineConfig)
    model: ModelConfig = field(default_factory=ModelConfig)
    train: TrainConfig = field(default_factory=TrainConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    session: SessionConfig = field(default_factory=SessionConfig)
    runtime: RuntimeConfig = field(default_factory=RuntimeConfig)
    fleet: FleetTopologyConfig = field(default_factory=FleetTopologyConfig)
    observability: ObservabilityConfig = field(
        default_factory=ObservabilityConfig)
    slo: SLOConfig = field(default_factory=SLOConfig)
    quality: QualityConfig = field(default_factory=QualityConfig)
    tracing: TracingConfig = field(default_factory=TracingConfig)
    profiling: ProfilingConfig = field(default_factory=ProfilingConfig)
    chaos: ChaosConfig = field(default_factory=ChaosConfig)
    control: ControlConfig = field(default_factory=ControlConfig)
    replay: ReplayConfig = field(default_factory=ReplayConfig)

    def __post_init__(self) -> None:
        if self.model.n_features is None:
            synced = dataclasses.replace(
                self.model, n_features=self.features.n_features
            )
            object.__setattr__(self, "model", synced)


def default_config() -> FrameworkConfig:
    return FrameworkConfig()


# ---------------------------------------------------------------------------
# Serialization: the whole config tree round-trips through JSON, so a
# deployment is one reviewable file (the reference's "edit config.py and the
# pipeline reshapes" property, config.py:31-65, without code edits).
# ---------------------------------------------------------------------------

_SECTIONS = {
    "features": FeatureConfig,
    "bus": BusConfig,
    "warehouse": WarehouseConfig,
    "engine": EngineConfig,
    "model": ModelConfig,
    "train": TrainConfig,
    "mesh": MeshConfig,
    "session": SessionConfig,
    "runtime": RuntimeConfig,
    "fleet": FleetTopologyConfig,
    "observability": ObservabilityConfig,
    "slo": SLOConfig,
    "quality": QualityConfig,
    "tracing": TracingConfig,
    "profiling": ProfilingConfig,
    "chaos": ChaosConfig,
    "control": ControlConfig,
    "replay": ReplayConfig,
}


def config_to_dict(cfg: FrameworkConfig) -> dict:
    """Nested plain-dict form (tuples become lists; JSON-ready).

    ``model.n_features`` is written as null: it is state *derived* from
    the feature schema (resolved by ``FrameworkConfig.__post_init__``),
    and persisting the resolved value would freeze it while an edited
    features section reshapes everything else."""
    d = dataclasses.asdict(cfg)
    d["model"]["n_features"] = None
    return d


def config_from_dict(data: dict) -> FrameworkConfig:
    """Rebuild a FrameworkConfig from (possibly partial) nested dicts.

    Unknown sections or keys raise — a typo'd config must fail loudly, not
    silently fall back to defaults.  JSON lists are coerced back to the
    tuples the frozen dataclasses expect.
    """
    sections = _SECTIONS
    unknown = set(data) - set(sections)
    if unknown:
        raise ValueError(f"unknown config sections: {sorted(unknown)}")
    kwargs = {}
    for name, cls in sections.items():
        if name not in data:
            continue
        section = data[name]
        field_names = {f.name for f in dataclasses.fields(cls)}
        bad = set(section) - field_names
        if bad:
            raise ValueError(f"unknown keys in [{name}]: {sorted(bad)}")
        coerced = {
            k: tuple(v) if isinstance(v, list) else v
            for k, v in section.items()
        }
        kwargs[name] = cls(**coerced)
    return FrameworkConfig(**kwargs)


def save_config(cfg: FrameworkConfig, path: str) -> str:
    import json

    with open(path, "w") as fh:
        json.dump(config_to_dict(cfg), fh, indent=2)
        fh.write("\n")
    return path


def load_config(path: str) -> FrameworkConfig:
    import json

    with open(path) as fh:
        return config_from_dict(json.load(fh))
