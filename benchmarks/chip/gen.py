"""Seeded inputs of the chip benchmark: serving weights and requests.

Everything here is drawn from `--seed` and a configuration file, never
loaded or fetched, and is vectorised. The program under test receives
only what these functions return.

Labels. Label sizes follow a power law n_r ∝ r^-alpha, scaled so the mean
is the published points per label. The multiset of sizes is fixed by the
configuration alone; a query document draws its labels in proportion to
their sizes.

Features. Each label owns `signature` feature ids. A point takes
`per_label` of them from each of its labels, plus background features from
a Zipf law over the vocabulary, up to `features_per_point` in all; values
are log-normal, signature features boosted, and each row has unit L2 norm
(a tf-idf bag of words).

Serving weights. W is a fully populated (L, D) BSR matrix of 128 x 128
fp32 blocks, N(0, sigma^2) entries with |w| < delta set to zero, generated
on the device one row block at a time from `fold_in(key, row_block)`, so
the reference can regenerate any row block without the program's copy.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class SparseRows:
    """Rows of a sparse (n, D) float32 matrix as COO triples, row-sorted."""
    n: int
    d: int
    rows: np.ndarray      # int32
    cols: np.ndarray      # int32
    vals: np.ndarray      # float32

    def to_dense(self) -> np.ndarray:
        out = np.zeros((self.n, self.d), np.float32)
        out[self.rows, self.cols] = self.vals
        return out


def label_sizes(cfg: dict) -> np.ndarray:
    """Points per label, by rank: the fixed multiset every seed shares."""
    L = cfg["n_labels"]
    a = cfg["label_power"]
    raw = np.arange(1, L + 1, dtype=np.float64) ** -a
    target = cfg["points_per_label"] * L
    sizes = raw * (target / raw.sum())
    # Deterministic rounding that keeps the total: floor, then hand the
    # remainder to the largest fractional parts.
    base = np.floor(sizes)
    short = int(round(target - base.sum()))
    order = np.argsort(-(sizes - base), kind="stable")
    base[order[:max(short, 0)]] += 1
    return np.clip(base, 1, cfg["n_train"]).astype(np.int64)


def make_features(cfg: dict, n_points: int, points: np.ndarray,
                  labels: np.ndarray, rng: np.random.Generator) -> SparseRows:
    """Unit-norm sparse feature rows for `n_points` points carrying the
    given (point, label) pairs."""
    D, L = cfg["n_features"], cfg["n_labels"]
    feat = cfg["features"]
    sig_len, per_label = feat["signature"], feat["per_label"]
    nnz = feat["features_per_point"]
    # Signature feature ids of every label (Zipf-distributed vocabulary
    # ids, the same law as the background).
    zipf_cdf = np.cumsum(np.arange(1, D + 1, dtype=np.float64) ** -feat["zipf"])
    zipf_cdf /= zipf_cdf[-1]
    vocab = rng.permutation(D)            # popularity rank -> feature id

    def draw(n):
        return vocab[np.minimum(np.searchsorted(zipf_cdf, rng.random(n)),
                                D - 1)]

    sig = draw(L * sig_len).reshape(L, sig_len)
    pick = rng.integers(0, sig_len, size=(labels.size, per_label))
    s_rows = np.repeat(points, per_label)
    s_cols = sig[labels[:, None], pick].reshape(-1)
    s_vals = feat["signature_boost"] * rng.lognormal(
        0.0, feat["value_sigma"], size=s_rows.size)
    key, first = np.unique(s_rows.astype(np.int64) * D + s_cols,
                           return_index=True)
    vals = s_vals[first]
    # Background words top each row up to `nnz` distinct features (or all
    # its signature words, where those are more); repeats are drawn again.
    for _ in range(8):
        have = np.bincount(key // D, minlength=n_points)
        short = np.maximum(nnz - have, 0)
        if not short.any():
            break
        b_rows = np.repeat(np.arange(n_points), short)
        b_key = b_rows.astype(np.int64) * D + draw(b_rows.size)
        b_key = np.setdiff1d(np.unique(b_key), key, assume_unique=True)
        b_vals = rng.lognormal(0.0, feat["value_sigma"], size=b_key.size)
        key = np.concatenate([key, b_key])
        vals = np.concatenate([vals, b_vals])
        order = np.argsort(key, kind="stable")
        key, vals = key[order], vals[order]
    rows, cols = key // D, key % D
    norm = np.sqrt(np.bincount(rows, weights=vals * vals,
                               minlength=n_points))
    vals = vals / norm[rows]
    return SparseRows(n_points, D, rows.astype(np.int32),
                      cols.astype(np.int32), vals.astype(np.float32))


def make_request_pool(cfg: dict, n_rows: int, seed: int) -> np.ndarray:
    """(n_rows, D) float32 query documents with the training rows'
    statistics: labels per point as published, each label drawn in
    proportion to its size."""
    rng = np.random.default_rng([seed, 2])
    sizes = label_sizes(cfg).astype(np.float64)
    n_pairs = int(round(n_rows * cfg["labels_per_point"]))
    labels = rng.choice(cfg["n_labels"], size=n_pairs, p=sizes / sizes.sum())
    points = rng.integers(0, n_rows, size=n_pairs)
    key = np.unique(points * cfg["n_labels"] + labels)
    points, labels = key // cfg["n_labels"], key % cfg["n_labels"]
    return make_features(cfg, n_rows, points, labels, rng).to_dense()


# -- serving weights ---------------------------------------------------------

def bsr_geometry(cfg: dict) -> dict:
    bl, bd = cfg["block_shape"]
    L, D = cfg["n_labels"], cfg["n_features"]
    nrb, ncb = -(-L // bl), -(-D // bd)
    return {"bl": bl, "bd": bd, "L": L, "D": D, "nrb": nrb, "ncb": ncb,
            "Lp": nrb * bl, "Dp": ncb * bd, "n_blocks": nrb * ncb}


def row_block(key, i, g: dict, sigma: float, delta: float):
    """Row block i of W as its (ncb, bl, bd) blocks: what the generator
    writes and what the reference regenerates."""
    import jax
    import jax.numpy as jnp
    bl, bd, ncb = g["bl"], g["bd"], g["ncb"]
    w = sigma * jax.random.normal(jax.random.fold_in(key, i),
                                  (ncb, bl, bd), jnp.float32)
    w = jnp.where(jnp.abs(w) < delta, 0.0, w)
    lab = i * bl + jnp.arange(bl)
    feat = jnp.arange(ncb)[:, None] * bd + jnp.arange(bd)[None, :]
    keep = (lab[None, :, None] < g["L"]) & (feat[:, None, :] < g["D"])
    return jnp.where(keep, w, 0.0)


def make_serving_blocks(cfg: dict, seed: int):
    """(blocks, block_rows, block_cols, row_ptr) on the device: every
    (row block, column block) present, row-major, in one jitted call."""
    import jax
    import jax.numpy as jnp
    g = bsr_geometry(cfg)
    w = cfg["weights"]
    key = weights_key(seed)

    @jax.jit
    def build(key):
        def body(i, buf):
            blk = row_block(key, i, g, w["sigma"], w["delta"])
            return jax.lax.dynamic_update_slice(buf, blk, (i * g["ncb"], 0, 0))
        buf = jnp.zeros((g["n_blocks"], g["bl"], g["bd"]), jnp.float32)
        return jax.lax.fori_loop(0, g["nrb"], body, buf)

    blocks = build(key)
    rows = jnp.repeat(jnp.arange(g["nrb"], dtype=jnp.int32), g["ncb"])
    cols = jnp.tile(jnp.arange(g["ncb"], dtype=jnp.int32), g["nrb"])
    ptr = jnp.arange(g["nrb"] + 1, dtype=jnp.int32) * g["ncb"]
    return blocks, rows, cols, ptr


def weights_key(seed: int):
    import jax
    return jax.random.key(seed % (2 ** 32))


def arrival_gaps(rate: float, seconds: float, rng: np.random.Generator):
    """Poisson inter-arrival gaps for one window: the same multiset for
    every seed (exponential quantiles at evenly spaced probabilities), in
    an order drawn from the seed."""
    n = max(1, int(round(rate * seconds)))
    u = (np.arange(n) + 0.5) / n
    return rng.permutation(-np.log1p(-u) / rate)
