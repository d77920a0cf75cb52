"""Chunked, windowed, normalized batch pipeline with device prefetch.

The TPU-first re-design of the reference's SQL dataloader stack
(sql_pytorch_dataloader.py:21-248):

- :class:`ChunkDataset` plays ``MySQLChunkLoader``: chunk ranges with
  window overlap + per-chunk normalization stats, against any
  :class:`~fmda_tpu.data.source.FeatureSource`.
- :class:`WindowBatches` plays ``MySQLBatchLoader``: one vectorized gather
  materialises every stride-1 window of a chunk, then yields fixed-shape
  batches (the last partial batch is zero-padded and masked, so every step
  hits the same compiled executable — no recompiles, no dynamic shapes).
- :func:`prefetch_batches` composes host batches in a daemon thread and
  places them on the device ahead of the step loop, so the device never
  waits on the host (the "infeed" half of SURVEY.md §7.2).
- :class:`TokenDataset` / :class:`TokenBatches` are the same two roles
  over a :class:`~fmda_tpu.data.source.TokenSource`: fixed-length id
  sequences cut from a packed stream, the target the input shifted by
  one, nothing normalised.
"""

from __future__ import annotations

from typing import (
    Callable,
    Iterable,
    Iterator,
    List,
    NamedTuple,
    Optional,
    Sequence,
    Tuple,
)

import numpy as np

from fmda_tpu.data.normalize import NormParams, chunk_norm_params, normalize
from fmda_tpu.data.source import FeatureSource, TokenSource
from fmda_tpu.data.windows import chunk_ranges, train_val_test_split, window_index_matrix


class Batch(NamedTuple):
    """One fixed-shape training batch: of feature windows, or (the
    shapes in brackets) of token sequences."""

    x: np.ndarray  # (B, window, F) float32, normalized  [(B, T) int32 ids]
    y: np.ndarray  # (B, n_classes) float32  [(B, T) int32: x shifted by one]
    mask: np.ndarray  # (B,) float32 — 0 for padded examples  [(B, T)]


class BatchGroup(NamedTuple):
    """Consecutive batches of a pass, stacked for one call into the
    compiled step (:func:`group_batches`)."""

    #: every leaf of a batch with two leading axes ``(k, 1)``: the
    #: group's size, and a unit axis that keeps the step axis out of the
    #: device's tiles (:func:`group_batches`)
    batches: Batch
    n_live: int  # the first n_live are the pass's; the rest is padding


class ChunkDataset:
    """Chunk ranges + per-chunk normalization stats over a source."""

    def __init__(
        self,
        source: FeatureSource,
        chunk_size: int,
        window: int,
        *,
        bid_levels: int = 0,
        ask_levels: int = 0,
        cache_chunks: int = 0,
    ) -> None:
        self.source = source
        self.window = window
        self.chunk_size = chunk_size
        self.cache_chunks = cache_chunks
        self.ranges = chunk_ranges(len(source), chunk_size, window)
        # per-chunk min-max stats: computed exactly once, here — every
        # epoch pass reuses them (they also ride into the compiled step
        # only through the already-normalized host batches, never
        # recomputed per pass)
        self.norm_params: List[NormParams] = [
            chunk_norm_params(
                source.fetch(r),
                source.x_fields,
                bid_levels=bid_levels,
                ask_levels=ask_levels,
            )
            for r in self.ranges
        ]
        from collections import OrderedDict

        self._window_cache: "OrderedDict[int, Tuple[np.ndarray, np.ndarray]]" = (
            OrderedDict()
        )

    def __len__(self) -> int:
        return len(self.ranges)

    def __getitem__(self, idx: int) -> Tuple[range, NormParams]:
        return self.ranges[idx], self.norm_params[idx]

    def windows(
        self, chunk_idx: int, norm_params: Optional[NormParams] = None
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Normalized stride-1 windows of one chunk: ``(x_windows,
        y_windows)``.

        The gather (source fetch + normalize + fancy-index copy) is the
        dominant host cost of an epoch; with ``cache_chunks > 0`` the
        result is kept in an LRU keyed on chunk index, so every pass
        after the first reuses it instead of redoing the work (host RAM
        bound: ``cache_chunks * chunk_size * window * F * 4`` bytes).
        Cached arrays are aliased, not copied — callers must treat them
        as read-only.  An explicit ``norm_params`` override (stats from
        a different chunk) bypasses the cache.
        """
        cacheable = norm_params is None and self.cache_chunks > 0
        if cacheable and chunk_idx in self._window_cache:
            self._window_cache.move_to_end(chunk_idx)
            return self._window_cache[chunk_idx]
        ids, chunk_params = self[chunk_idx]
        params = norm_params if norm_params is not None else chunk_params
        x = normalize(self.source.fetch(ids), params)
        y = np.asarray(self.source.fetch_targets(ids), np.float32)
        widx = window_index_matrix(len(x), self.window)
        x_windows = x[widx]  # (n_windows, window, F)
        y_windows = y[widx[:, -1]] if len(widx) else y[:0]
        if cacheable:
            self._window_cache[chunk_idx] = (x_windows, y_windows)
            while len(self._window_cache) > self.cache_chunks:
                self._window_cache.popitem(last=False)
        return x_windows, y_windows

    @property
    def final_norm_params(self) -> NormParams:
        """The last chunk's stats — the reference persists these for
        val/test/serving (sql_pytorch_dataloader.py:147-153)."""
        return self.norm_params[-1]

    def split(
        self, val_size: float = 0.1, test_size: float = 0.1
    ) -> Tuple[Sequence[int], Sequence[int], Sequence[int]]:
        return train_val_test_split(len(self), val_size, test_size)


class WindowBatches:
    """Fixed-shape sliding-window batches for one chunk."""

    def __init__(
        self,
        dataset: ChunkDataset,
        chunk_idx: int,
        batch_size: int,
        *,
        norm_params: Optional[NormParams] = None,
        drop_remainder: bool = False,
    ) -> None:
        self.x_windows, self.y_windows = dataset.windows(
            chunk_idx, norm_params)
        self.batch_size = batch_size
        self.drop_remainder = drop_remainder

    def __len__(self) -> int:
        n = len(self.x_windows)
        if self.drop_remainder:
            return n // self.batch_size
        return (n + self.batch_size - 1) // self.batch_size

    def __iter__(self) -> Iterator[Batch]:
        n = len(self.x_windows)
        bs = self.batch_size
        for start in range(0, n, bs):
            xb = self.x_windows[start : start + bs]
            yb = self.y_windows[start : start + bs]
            valid = len(xb)
            if valid < bs:
                if self.drop_remainder:
                    return
                pad = bs - valid
                xb = np.concatenate([xb, np.zeros((pad,) + xb.shape[1:], xb.dtype)])
                yb = np.concatenate([yb, np.zeros((pad,) + yb.shape[1:], yb.dtype)])
            mask = np.zeros(bs, np.float32)
            mask[:valid] = 1.0
            yield Batch(xb, yb, mask)


class TokenDataset:
    """Fixed-length sequences cut from a packed token stream, in chunks.

    Sequence ``i`` reads tokens ``i*T .. i*T + T`` of the stream (``T`` =
    ``window``): its input is the first ``T`` of them and its target the
    last ``T``, so consecutive sequences share one token and every
    position has a next token to predict.  Nothing is padded inside a
    sequence and nothing is normalised; documents cross sequence
    boundaries as the stream has them.  A chunk is
    ``max(chunk_size // window, 1)`` consecutive sequences — the unit of
    the train/validation/test split and of the placed-batch cache, as a
    chunk of rows is for :class:`ChunkDataset`.
    """

    def __init__(self, source: TokenSource, chunk_size: int,
                 window: int) -> None:
        self.source = source
        self.window = window
        self.chunk_size = chunk_size
        self.n_sequences = max(len(source) - 1, 0) // window
        self.per_chunk = max(chunk_size // window, 1)

    def __len__(self) -> int:
        return -(-self.n_sequences // self.per_chunk)

    def sequences(self, chunk_idx: int) -> Tuple[np.ndarray, np.ndarray]:
        """``(inputs, targets)`` of one chunk, each (n, window) int32."""
        first = chunk_idx * self.per_chunk
        n = min(self.per_chunk, self.n_sequences - first)
        t = self.window
        ids = np.asarray(self.source.fetch_tokens(
            first * t, (first + n) * t + 1), np.int32)
        return ids[:-1].reshape(n, t), ids[1:].reshape(n, t)

    def split(
        self, val_size: float = 0.1, test_size: float = 0.1
    ) -> Tuple[Sequence[int], Sequence[int], Sequence[int]]:
        return train_val_test_split(len(self), val_size, test_size)


class TokenBatches:
    """Fixed-shape batches of one chunk's sequences; the last partial
    batch is padded with all-masked sequences of id 0."""

    def __init__(self, dataset: TokenDataset, chunk_idx: int,
                 batch_size: int) -> None:
        self.x, self.y = dataset.sequences(chunk_idx)
        self.batch_size = batch_size

    def __len__(self) -> int:
        return -(-len(self.x) // self.batch_size)

    def __iter__(self) -> Iterator[Batch]:
        bs = self.batch_size
        for start in range(0, len(self.x), bs):
            xb, yb = self.x[start:start + bs], self.y[start:start + bs]
            mask = np.zeros((bs, xb.shape[1]), np.float32)
            mask[:len(xb)] = 1.0
            if len(xb) < bs:
                pad = np.zeros((bs - len(xb), xb.shape[1]), np.int32)
                xb, yb = np.concatenate([xb, pad]), np.concatenate([yb, pad])
            yield Batch(xb, yb, mask)


def group_batches(batches: Iterable[Batch], k: int
                  ) -> Iterator[BatchGroup]:
    """Stack each ``k`` consecutive host batches into a
    :class:`BatchGroup`.

    The order is the iterable's.  Where the batches do not divide by
    ``k`` the last group is padded with zeros to the same shape and says
    how many of its batches are live, so one compiled program serves a
    pass of any length.

    A leaf of shape ``s`` is stacked as ``(k, 1) + s``, not ``(k,) + s``.
    A TPU lays an array out in tiles over the two axes that pad least,
    and beside a batch axis of 256 a step axis of 16 is one of them: a
    step's batch is then one row of every tile, and slicing it out cost
    36.8 us of a 126 us train step (my chip run, PR 29; PERF.md section
    6).  With the unit axis between them the tile takes that axis
    instead, the step axis stays outermost on the device, and a step's
    batch is one contiguous piece in the layout a batch placed alone
    has."""
    def stacked(pending: List[Batch]) -> BatchGroup:
        def stack(*leaves):
            out = np.zeros((k, 1) + leaves[0].shape, leaves[0].dtype)
            for i, leaf in enumerate(leaves):
                out[i, 0] = leaf
            return out
        return BatchGroup(Batch(*map(stack, *pending)), len(pending))

    pending: List[Batch] = []
    for batch in batches:
        pending.append(batch)
        if len(pending) == k:
            yield stacked(pending)
            pending = []
    if pending:
        yield stacked(pending)


def prefetch_batches(
    batches: Iterable[Batch],
    place: Callable[[Batch], Batch],
    *,
    depth: int = 2,
) -> Iterator[Batch]:
    """Depth-N double-buffered input pipeline.

    Host composition runs in a daemon thread (:func:`background_compose`
    — so WindowBatches gathers for chunk k+1 overlap the device steps of
    chunk k), each composed batch is handed to ``place`` immediately
    (``jax.device_put`` dispatches async — the transfer also overlaps),
    and up to ``depth`` placed batches ride ahead of the consumer.

    The refill runs in the consumer's ``next()``: the pull from the
    composer's queue under the host span ``input_compose`` and the
    placement under ``input_place`` (utils/tracing.py ``span``; both
    nest inside the step loop's ``train_next_batch``).  The wait itself
    is measured by the consumer, around that ``next()``
    (``train_input_stall_seconds``, Trainer._run_batches); the first
    ``depth`` pulls include pipeline warm-up by design.

    ``depth=0`` degrades to a synchronous place-per-batch loop with no
    background thread — the seed behavior.
    """
    from fmda_tpu.utils.tracing import span

    if depth <= 0:
        def sync() -> Iterator[Batch]:
            for b in batches:
                with span("input_place"):
                    out = place(b)
                yield out
        return sync()

    import collections

    def run() -> Iterator[Batch]:
        queue: collections.deque = collections.deque()
        it = iter(background_compose(batches, depth=depth))
        exhausted = False
        while True:
            while not exhausted and len(queue) < depth:
                with span("input_compose"):
                    b = next(it, None)
                if b is None:
                    exhausted = True
                    break
                with span("input_place"):
                    queue.append(place(b))
            if not queue:
                return
            yield queue.popleft()

    return run()


def background_compose(
    batches: Iterable[Batch], depth: int = 2
) -> Iterator[Batch]:
    """Run a host-side batch composer in a daemon thread, handing batches
    over a bounded queue.

    Host composition (window gather + per-ticker normalization + concat —
    ``MultiTickerDataset.mixed_batches`` costs ~12 ms/batch at the
    50-ticker config) otherwise serialises with the device step loop:
    the generator composes batch ``i+1`` only when the consumer pulls
    it.  Behind this wrapper the composer works while the device
    computes, so the steady-state step cost is ``max(compose, step)``
    instead of their sum.  Compose errors propagate to the consumer at
    the point of the failed batch; the bounded queue keeps at most
    ``depth`` batches of host memory in flight.
    """
    import queue as queue_mod
    import threading

    q: queue_mod.Queue = queue_mod.Queue(maxsize=depth)
    stop = threading.Event()
    _DONE = object()

    def _put(item) -> bool:
        # bounded put that gives up when the consumer is gone — a plain
        # q.put would park this thread forever (holding batch memory) if
        # the consumer abandons the generator mid-epoch
        while not stop.is_set():
            try:
                q.put(item, timeout=0.2)
                return True
            except queue_mod.Full:
                continue
        return False

    def worker():
        try:
            for b in batches:
                if not _put(b):
                    return
            _put(_DONE)
        except BaseException as e:  # noqa: BLE001 - relayed to consumer
            _put(e)

    t = threading.Thread(target=worker, daemon=True,
                         name="fmda-batch-compose")
    t.start()
    try:
        while True:
            item = q.get()
            if item is _DONE:
                return
            if isinstance(item, BaseException):
                raise item
            yield item
    finally:
        # consumer done, errored, or close()d the generator: release the
        # worker and drop any queued batches
        stop.set()
