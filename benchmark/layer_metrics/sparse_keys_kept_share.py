"""Share of the causal (query, key) pairs that attention keeps: the keys
the selection counted over the window's training passes
(``sparse_keys_kept_total``) over the causal pairs of the rows it
counted (``sparse_query_rows_total``, whole sequences of the cell's
length), all layers together.  23.44 % at 16,384 tokens and 2,048 keys;
a change that keeps fewer shows here (and fails ``correct``)."""

from benchmark.harness import sparse_decoder_flops as flops

NAME = "sparse_keys_kept_share"
UNIT = "%"
LAYER = "kernels"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "train_samples_per_s"


def read(record):
    sparse = record.get("sparse")
    if not sparse:
        return None
    rows = sum(sparse["query_rows_per_train_step"])
    if not rows:
        return None
    seq = sparse["seq_len"]
    causal = rows / seq * flops.causal_pairs(seq)
    return 100.0 * sum(sparse["keys_kept_per_train_step"]) / causal
