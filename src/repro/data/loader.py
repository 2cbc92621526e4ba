"""Federated round-batch assembly.

Implements the paper's round protocol on the host side:
* client sampling (partial participation, rate r),
* eq. (3) minibatch sizing B_k ∝ |D_k| (padded to max B_k with
  zero-weight rows so client batches stack into a (C, B_k, ...) tensor),
* T local-iteration minibatches per round: leaves (T, C, Bk, ...).

The draw (:func:`round_rows`, global row ids) is separate from the copy,
so the rows can be gathered from a pool that stays on the device.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Sequence

import numpy as np

from repro.core.split import client_minibatch_sizes


@dataclass
class FederatedData:
    """Per-client datasets: x[i], y[i] are client i's arrays."""

    xs: List[np.ndarray]
    ys: List[np.ndarray]

    @property
    def num_clients(self) -> int:
        return len(self.xs)

    @property
    def sizes(self) -> np.ndarray:
        return np.array([len(y) for y in self.ys], np.int64)

    @classmethod
    def from_partition(cls, x, y, parts: Sequence[np.ndarray]):
        return cls(xs=[x[p] for p in parts], ys=[y[p] for p in parts])


def sample_clients(num_clients: int, num_selected: int,
                   rng: np.random.Generator) -> np.ndarray:
    return rng.choice(num_clients, size=num_selected, replace=False)


def pool_arrays(data: FederatedData):
    """Every client's rows concatenated into one pool, plus a sentinel row
    (index N, all zeros, label 0) that padding rows point at: x (N + 1,
    ...) in the data's own dtype and labels (N + 1,) int32. Client k's
    rows start at ``sum(sizes[:k])``."""
    x0 = data.xs[0]
    x = np.concatenate([*data.xs, np.zeros((1,) + x0.shape[1:], x0.dtype)],
                       dtype=x0.dtype)
    y = np.concatenate([*data.ys, np.zeros(1, np.int32)], dtype=np.int32)
    return x, y


def round_rows(data: FederatedData, selected: np.ndarray,
               server_batch: int, local_iters: int,
               rng: np.random.Generator):
    """Draw one round's rows: global row ids (T, C, Bk) int32 into
    :func:`pool_arrays` (padding rows hold the sentinel N), and the
    selected clients' sizes (C,) float32 for eq. (10) aggregation.

    eq. (3) sizing B_k ∝ |D_k|, padded to max B_k; one ``rng.choice``
    per slot and local step, slot-major."""
    all_sizes = data.sizes
    sizes = all_sizes[selected]
    bks = client_minibatch_sizes(sizes, server_batch)
    starts = np.concatenate([[0], np.cumsum(all_sizes)[:-1]])
    T, C = local_iters, len(selected)
    rows = np.full((T, C, int(bks.max())), all_sizes.sum(), np.int32)
    for ci, k in enumerate(selected):
        n, bk = int(all_sizes[k]), int(bks[ci])
        for t in range(T):
            rows[t, ci, :bk] = starts[k] + rng.choice(n, size=bk,
                                                      replace=n < bk)
    return rows, sizes.astype(np.float32)


def round_batches(data: FederatedData, selected: np.ndarray,
                  server_batch: int, local_iters: int,
                  rng: np.random.Generator,
                  x_key: str = "x") -> Dict[str, np.ndarray]:
    """Build one round's batches: {'x': (T,C,Bk,...), 'labels', 'weights'},
    plus 'sizes' (C,) for eq. (10) aggregation. The host gather of
    :func:`round_rows`' draw from :func:`pool_arrays`; padding rows are
    zeros with label 0 and weight 0."""
    rows, sizes = round_rows(data, selected, server_batch, local_iters, rng)
    x, y = pool_arrays(data)
    return {x_key: x[rows], "labels": y[rows],
            "weights": (rows < len(y) - 1).astype(np.float32),
            "sizes": sizes}


def lm_round_batches(docs_by_client: List[np.ndarray], selected: np.ndarray,
                     server_batch: int, local_iters: int,
                     rng: np.random.Generator) -> Dict[str, np.ndarray]:
    """LM variant: docs (n_k, L) int32 per client. tokens = doc[:-1],
    labels = doc[1:], next-token prediction."""
    sizes = np.array([len(d) for d in docs_by_client])[selected]
    bks = client_minibatch_sizes(sizes, server_batch)
    bk_max = int(bks.max())
    T, C = local_iters, len(selected)
    L = docs_by_client[0].shape[1]

    toks = np.zeros((T, C, bk_max, L - 1), np.int32)
    labs = np.zeros((T, C, bk_max, L - 1), np.int32)
    ws = np.zeros((T, C, bk_max, L - 1), np.float32)
    for ci, k in enumerate(selected):
        dk = docs_by_client[k]
        bk = int(bks[ci])
        for t in range(T):
            idx = rng.choice(len(dk), size=bk, replace=len(dk) < bk)
            toks[t, ci, :bk] = dk[idx, :-1]
            labs[t, ci, :bk] = dk[idx, 1:]
            ws[t, ci, :bk] = 1.0
    return {"tokens": toks, "labels": labs, "weights": ws,
            "sizes": sizes.astype(np.float32)}
