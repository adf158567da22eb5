"""Seeded dense query rows: text embeddings over a large label space.

Each query draws its labels as `gen.make_request_pool` draws a document's:
`labels_per_point` on average, each label in proportion to its size
(`gen.label_sizes`, the power law of the configuration). Each label owns a
centroid N(0, I/D); the query is the sum of its labels' centroids over the
square root of their count, plus noise N(0, noise^2 I/D), scaled to unit
L2 norm. Only the centroids of the labels drawn are made, so a million
labels cost nothing here. The serving weights are `gen.make_serving_blocks`,
the same for sparse and dense queries.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import gen


@dataclasses.dataclass
class DenseQueries:
    """(n, D) unit-norm float32 rows and the (point, label) pairs drawn."""
    x: np.ndarray
    points: np.ndarray
    labels: np.ndarray


def dense_queries(cfg: dict, n_rows: int, seed: int) -> DenseQueries:
    rng = np.random.default_rng([seed, 2])
    L, D = cfg["n_labels"], cfg["n_features"]
    sizes = gen.label_sizes(cfg).astype(np.float64)
    n_pairs = int(round(n_rows * cfg["labels_per_point"]))
    labels = rng.choice(L, size=n_pairs, p=sizes / sizes.sum())
    points = rng.integers(0, n_rows, size=n_pairs)
    key = np.unique(points.astype(np.int64) * L + labels)
    points, labels = key // L, key % L
    uniq, which = np.unique(labels, return_inverse=True)
    centroids = rng.standard_normal((len(uniq), D)) / np.sqrt(D)
    q = np.zeros((n_rows, D))
    np.add.at(q, points, centroids[which])
    q /= np.sqrt(np.maximum(np.bincount(points, minlength=n_rows), 1))[:, None]
    q += cfg["queries"]["noise"] * rng.standard_normal((n_rows, D)) / np.sqrt(D)
    x = q / np.linalg.norm(q, axis=1, keepdims=True)
    return DenseQueries(x.astype(np.float32), points, labels)


def make_request_pool(cfg: dict, n_rows: int, seed: int) -> np.ndarray:
    """(n_rows, D) float32 query rows, the pool a serving window draws
    its requests from."""
    return dense_queries(cfg, n_rows, seed).x
