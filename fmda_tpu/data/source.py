"""Pluggable feature and token sources for the data pipeline.

The reference hard-wires training to a live MariaDB cursor
(sql_pytorch_dataloader.py:62-65, 227-236).  Here the pipeline reads through
a small protocol so the same trainer runs against the streaming warehouse,
in-memory arrays (tests/benchmarks), or any columnar store.
Row ids are 1-based, matching the reference's AUTO_INCREMENT ids.
"""

from __future__ import annotations

from typing import Protocol, Sequence, Tuple

import numpy as np


class FeatureSource(Protocol):
    """Columnar access to the joined feature table + target view."""

    @property
    def x_fields(self) -> Tuple[str, ...]:
        """Feature column names, in schema order."""
        ...

    def __len__(self) -> int:
        """Number of rows available (max id)."""
        ...

    def fetch(self, ids: Sequence[int]) -> np.ndarray:
        """Feature rows for 1-based ids, shape (len(ids), F); NaNs/None
        are the caller's responsibility to have filled (IFNULL parity)."""
        ...

    def fetch_targets(self, ids: Sequence[int]) -> np.ndarray:
        """Target rows for 1-based ids, shape (len(ids), n_classes)."""
        ...


class ArraySource:
    """In-memory :class:`FeatureSource` over numpy arrays."""

    def __init__(
        self,
        x: np.ndarray,
        y: np.ndarray,
        x_fields: Sequence[str],
    ) -> None:
        assert x.ndim == 2 and y.ndim == 2 and len(x) == len(y)
        assert x.shape[1] == len(x_fields)
        self._x = np.asarray(x, np.float32)
        self._y = np.asarray(y, np.float32)
        self._fields = tuple(x_fields)

    @property
    def x_fields(self) -> Tuple[str, ...]:
        return self._fields

    def __len__(self) -> int:
        return len(self._x)

    def _to_index(self, ids: Sequence[int]) -> np.ndarray:
        idx = np.asarray(list(ids), dtype=np.int64) - 1  # 1-based -> 0-based
        if idx.size and (idx.min() < 0 or idx.max() >= len(self._x)):
            raise IndexError(
                f"row ids out of range 1..{len(self._x)}: "
                f"[{idx.min() + 1}, {idx.max() + 1}]"
            )
        return idx

    def fetch(self, ids: Sequence[int]) -> np.ndarray:
        return np.nan_to_num(self._x[self._to_index(ids)], nan=0.0)

    def fetch_targets(self, ids: Sequence[int]) -> np.ndarray:
        return self._y[self._to_index(ids)]


class TokenSource(Protocol):
    """A stream of token ids, for the families trained on next-token
    prediction.  Positions are 0-based."""

    @property
    def vocab_size(self) -> int:
        """Every id is in ``0 .. vocab_size - 1``."""
        ...

    def __len__(self) -> int:
        """Number of tokens available."""
        ...

    def fetch_tokens(self, start: int, stop: int) -> np.ndarray:
        """Ids of positions ``start .. stop - 1``, int32."""
        ...


class TokenArraySource:
    """In-memory :class:`TokenSource` over one int array (documents
    already joined by whatever end-of-document id the corpus uses)."""

    def __init__(self, ids: np.ndarray, vocab_size: int) -> None:
        ids = np.asarray(ids)
        assert ids.ndim == 1 and np.issubdtype(ids.dtype, np.integer)
        if ids.size and (ids.min() < 0 or ids.max() >= vocab_size):
            raise ValueError(
                f"token ids outside 0..{vocab_size - 1}: "
                f"[{ids.min()}, {ids.max()}]")
        self._ids = ids.astype(np.int32)
        self._vocab_size = int(vocab_size)

    @property
    def vocab_size(self) -> int:
        return self._vocab_size

    def __len__(self) -> int:
        return len(self._ids)

    def fetch_tokens(self, start: int, stop: int) -> np.ndarray:
        return self._ids[start:stop]
