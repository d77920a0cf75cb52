"""``train_samples_per_s`` counts valid windows — the mask sum — never
the padded lanes of a chunk's last batch."""

import numpy as np

from benchmark.harness import catalog
from benchmark.harness.corpus import make_corpus


def test_valid_windows_is_the_mask_sum():
    from fmda_tpu.data.pipeline import ChunkDataset, WindowBatches
    from fmda_tpu.data.source import ArraySource

    driver = catalog.load_driver("train_epochs")
    x, y = make_corpus(1000, 6, seed=1)
    source = ArraySource(x, y, [f"f{i}" for i in range(6)])
    # the paper's chunk 100 at batch 256: 100 windows in 256 lanes (chunk 0,
    # which starts at row 30, has 41)
    dataset = ChunkDataset(source, 100, 30)
    chunks = list(range(len(dataset)))[:8]
    valid, lanes, steps = driver.valid_windows(dataset, chunks, 256)
    mask_sum = sum(float(b.mask.sum()) for i in chunks
                   for b in WindowBatches(dataset, i, 256))
    assert valid == mask_sum == 41 + 7 * 100
    assert lanes == 8 * 256 and steps == 8
    assert valid / lanes < 0.4


def test_corpus_is_seeded_and_labels_are_not_degenerate():
    x1, y1 = make_corpus(5000, 8, seed=3)
    x2, y2 = make_corpus(5000, 8, seed=3)
    assert np.array_equal(x1, x2) and np.array_equal(y1, y2)
    assert not np.array_equal(x1, make_corpus(5000, 8, seed=4)[0])
    assert np.isfinite(x1).all()
    share = y1.mean(axis=0)
    assert np.all(share > 0.05) and np.all(share < 0.5)
    assert share[0] > share[1] and share[2] > share[3]


def test_train_flops_leave_the_recurrent_matrix_out_for_the_ssm():
    from benchmark.harness import flops

    gru = flops.train_flops_per_window(30, 108, 32, 4)
    ssm = flops.train_flops_per_window(30, 108, 32, 4, cell="ssm")
    # per direction and step the gru multiplies by W_hh (2*H*3H), the
    # ssm's transition is elementwise; forward + backward is 3x forward
    assert gru - ssm == 3 * 2 * 30 * 2 * 32 * 3 * 32
    assert ssm == 3 * (2 * 2 * 30 * 108 * 96 + 2 * 96 * 4)
