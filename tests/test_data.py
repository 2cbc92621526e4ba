import numpy as np
import pytest

from repro.data.loader import (FederatedData, lm_round_batches, pool_arrays,
                               round_batches, round_rows, sample_clients)
from repro.data.partition import dirichlet_skew, partition, quantity_skew
from repro.data.synthetic import gaussian_images, token_stream


def test_quantity_skew_class_bound():
    rng = np.random.default_rng(0)
    labels = np.repeat(np.arange(10), 100)
    parts = quantity_skew(labels, num_clients=20, alpha=2, num_classes=10,
                          rng=rng)
    assert len(parts) == 20
    all_idx = np.concatenate(parts)
    assert len(all_idx) <= len(labels)
    for p in parts:
        classes = np.unique(labels[p])
        assert len(classes) <= 2  # at most alpha classes per client


def test_dirichlet_skew_partitions_everything():
    rng = np.random.default_rng(0)
    labels = np.repeat(np.arange(5), 50)
    parts = dirichlet_skew(labels, num_clients=8, beta=0.5, num_classes=5,
                           rng=rng)
    total = sum(len(p) for p in parts)
    assert total == len(labels)
    assert min(len(p) for p in parts) >= 2


def test_dirichlet_strong_skew_missing_classes():
    rng = np.random.default_rng(1)
    labels = np.repeat(np.arange(10), 100)
    parts = dirichlet_skew(labels, num_clients=10, beta=0.05, num_classes=10,
                           rng=rng)
    missing = sum(len(np.unique(labels[p])) < 10 for p in parts)
    assert missing >= 5  # strong skew -> most clients missing classes


def test_partition_dispatch():
    labels = np.repeat(np.arange(4), 25)
    with pytest.raises(AssertionError):
        partition(labels, 4)  # neither alpha nor beta
    p1 = partition(labels, 4, alpha=2, seed=0)
    p2 = partition(labels, 4, beta=0.5, seed=0)
    assert len(p1) == len(p2) == 4


def test_round_batches_shapes_and_weights():
    x, y = gaussian_images(200, num_classes=4, seed=0)
    parts = partition(y, 10, beta=0.3, num_classes=4, seed=0)
    data = FederatedData.from_partition(x, y, parts)
    rng = np.random.default_rng(0)
    sel = sample_clients(10, 4, rng)
    rb = round_batches(data, sel, server_batch=32, local_iters=3, rng=rng)
    T, C, Bk = rb["labels"].shape
    assert (T, C) == (3, 4)
    assert rb["x"].shape[:3] == (3, 4, Bk)
    # eq (3): per-client real rows proportional to |D_k|
    for ci in range(C):
        real = rb["weights"][0, ci].sum()
        assert real >= 1
    assert rb["sizes"].shape == (4,)


def test_round_rows_are_global_ids_with_a_sentinel():
    rng = np.random.default_rng(0)
    sizes = (7, 30, 3, 16)
    data = FederatedData(
        xs=[rng.normal(size=(n, 2, 2, 3)).astype(np.float32) for n in sizes],
        ys=[rng.integers(0, 10, size=n) for n in sizes])
    x, y = pool_arrays(data)
    N = sum(sizes)
    assert x.shape == (N + 1, 2, 2, 3) and x.dtype == np.float32
    assert y.shape == (N + 1,) and y.dtype == np.int32
    assert not x[N].any() and y[N] == 0

    sel = np.array([3, 0, 1])
    rows, got_sizes = round_rows(data, sel, server_batch=20, local_iters=2,
                                 rng=np.random.default_rng(4))
    assert rows.dtype == np.int32 and rows.shape[:2] == (2, 3)
    np.testing.assert_array_equal(got_sizes, np.array(sizes)[sel])
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    for ci, k in enumerate(sel):
        real = rows[:, ci][rows[:, ci] < N]
        assert ((real >= starts[k]) & (real < starts[k] + sizes[k])).all()
    assert (rows == N).any()                   # client 0 is padded

    rb = round_batches(data, sel, server_batch=20, local_iters=2,
                       rng=np.random.default_rng(4))
    np.testing.assert_array_equal(rb["x"], x[rows])
    np.testing.assert_array_equal(rb["labels"], y[rows])
    np.testing.assert_array_equal(rb["weights"], rows < N)


def test_lm_round_batches_next_token():
    docs, _ = token_stream(20, doc_len=17, vocab=50, seed=0)
    by_client = [docs[:10], docs[10:]]
    rng = np.random.default_rng(0)
    rb = lm_round_batches(by_client, np.array([0, 1]), server_batch=8,
                          local_iters=2, rng=rng)
    assert rb["tokens"].shape == rb["labels"].shape
    assert rb["tokens"].shape[-1] == 16
    # next-token alignment: labels are tokens shifted by one
    t, c, b = 0, 0, 0
    # can't check alignment directly (random docs), but ranges must be valid
    assert rb["tokens"].max() < 50 and rb["labels"].max() < 50


def test_token_stream_domain_skew():
    docs, domains = token_stream(100, doc_len=64, vocab=200, num_domains=4,
                                 seed=0)
    # different domains -> different unigram distributions
    def hist(d):
        sel = docs[domains == d].reshape(-1)
        h = np.bincount(sel, minlength=200).astype(float)
        return h / h.sum()
    h0, h1 = hist(0), hist(1)
    tv = 0.5 * np.abs(h0 - h1).sum()
    assert tv > 0.3  # strongly different


def test_gaussian_images_learnable_structure():
    x, y = gaussian_images(500, num_classes=4, seed=0)
    assert x.shape == (500, 32, 32, 3)
    # class means differ
    m0 = x[y == 0].mean(axis=0)
    m1 = x[y == 1].mean(axis=0)
    assert np.abs(m0 - m1).mean() > 0.1
