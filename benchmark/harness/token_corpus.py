"""A seeded token corpus made in bulk: one packed stream of ids.

Documents of log-normal length are drawn one after another, each ended
by the end-of-document id, and joined without padding until the stream
is long enough; sequences are cut from the stream wherever they fall, so
documents cross sequence boundaries.  Inside a document the ids are
independent draws from a Zipf law over the vocabulary without the
end-of-document id: rank ``r`` has weight ``r ** -exponent``, and which
id holds which rank is a seeded permutation.  The values matter for two
things only: the loss is finite and learnable (the unigram law alone
takes it from ``ln V`` towards the law's entropy), and a few ids carry
most of the tokens, so that a router sees the same rows again and again
and its experts fill unevenly.
"""

from __future__ import annotations

import numpy as np


def make_token_stream(n_tokens: int, vocab_size: int, seed: int, *,
                      zipf_exponent: float = 1.0,
                      doc_median_tokens: float = 1000.0,
                      doc_sigma: float = 1.0, eod_id: int = 0) -> np.ndarray:
    """``n_tokens`` ids in ``0 .. vocab_size - 1``, int32."""
    rng = np.random.default_rng([seed, 28])
    ids = np.delete(np.arange(vocab_size, dtype=np.int32), eod_id)
    rng.shuffle(ids)  # ids[r] holds rank r + 1
    weights = np.arange(1, len(ids) + 1, dtype=np.float64) ** -zipf_exponent
    cdf = np.cumsum(weights)
    cdf /= cdf[-1]
    ranks = np.searchsorted(cdf, rng.random(n_tokens), side="right")
    stream = ids[np.minimum(ranks, len(ids) - 1)]
    # document ends: a running sum of log-normal lengths, each at least 2
    # (one token and its end-of-document id)
    mean_len = doc_median_tokens * np.exp(0.5 * doc_sigma ** 2)
    n_docs = int(2 * n_tokens / mean_len) + 16
    while True:
        lengths = np.maximum(rng.lognormal(
            np.log(doc_median_tokens), doc_sigma, n_docs), 2.0).astype(
                np.int64)
        ends = np.cumsum(lengths) - 1
        if ends[-1] >= n_tokens - 1:
            break
        n_docs *= 2
    stream[ends[ends < n_tokens]] = eod_id
    return stream


def zipf_entropy_nats(vocab_size: int, exponent: float = 1.0) -> float:
    """Entropy of the id law (end-of-document ids aside): where a model
    that has learnt the unigram frequencies and nothing else ends up."""
    w = np.arange(1, vocab_size, dtype=np.float64) ** -exponent
    p = w / w.sum()
    return float(-(p * np.log(p)).sum())
