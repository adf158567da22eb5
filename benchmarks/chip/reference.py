"""The plain reference and the comparison that decides `correct`.

Nothing here imports the program (`repro`) or takes anything it made.

Scores of sampled request rows against W, regenerated row block by row
block from the seed (`gen.row_block`), in float32 at the highest matmul
precision: the exact scores. Each served (row, label, score) is read as a
share of sum_j |x_j w_lj|, the scale of that score's rounding:

  score_err_mean  the mean distance of a served score from the
                  reference's score of the same (row, label): the
                  precision of the products, steady from seed to seed;
  score_err       the largest such distance;
  rank_gap        how far the reference score of a served label lies below
                  the reference's k-th best score of that row (0 when it
                  is in the reference's top k).

The checks file of a cell names the numbers that decide `correct`. The
largest distance and the rank gap swing with the tail of the rounding
errors and are printed, not compared (PERF.md gives the readings).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

import gen


class ServeRef(NamedTuple):
    """Reference readings of n rows over the L real labels, each (n, L)."""
    scores: np.ndarray          # float32 at the highest precision
    scale: np.ndarray           # sum_j |x_j w_lj|


@functools.partial(jax.jit, static_argnames=("g", "sigma", "delta"))
def _ref_pass(key, x, *, g, sigma, delta):
    """(scores, abs_scale), each (n, Lp), one row block at a time."""
    hi = jax.lax.Precision.HIGHEST

    def one(i):
        blk = gen.row_block(key, i, g, sigma, delta)        # (ncb, bl, bd)
        w = blk.transpose(1, 0, 2).reshape(g["bl"], g["Dp"])
        return (jnp.dot(x, w.T, precision=hi),
                jnp.dot(jnp.abs(x), jnp.abs(w).T, precision=hi))

    out = jax.lax.map(one, jnp.arange(g["nrb"]))            # (nrb, n, bl)
    n = x.shape[0]
    return tuple(o.transpose(1, 0, 2).reshape(n, g["Lp"]) for o in out)


def serve_reference(cfg: dict, seed: int, x: np.ndarray, *,
                    chunk: int = 256) -> ServeRef:
    """Reference readings of rows x (n, D)."""
    g = gen.bsr_geometry(cfg)
    w = cfg["weights"]
    key = gen.weights_key(seed)
    xs = np.zeros((len(x), g["Dp"]), np.float32)
    xs[:, :g["D"]] = x
    parts = []
    for s in range(0, len(xs), chunk):
        out = _ref_pass(key, jnp.asarray(xs[s:s + chunk]), g=_Frozen(g),
                        sigma=float(w["sigma"]), delta=float(w["delta"]))
        parts.append([np.asarray(o)[:, :g["L"]] for o in out])
    return ServeRef(*(np.concatenate(p) for p in zip(*parts)))


class _Frozen(dict):
    """A hashable dict, for static jit arguments."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def reference_topk(scores: np.ndarray, k: int):
    """(labels, scores) of the k best per row, best first."""
    idx = np.argsort(-scores, axis=1, kind="stable")[:, :k]
    return idx, np.take_along_axis(scores, idx, axis=1)


def judge_answers(labels, scores, ref: ServeRef, k: int) -> dict:
    """The serving numbers for served (labels, scores) of shape (n, k)
    against the reference's readings of the same rows.

    bad: answers that cannot be judged at all — wrong shape, an id out of
    range, a repeated id. Each is counted, and the row is left out of the
    two shares."""
    n, L = ref.scores.shape
    labels = np.asarray(labels)
    scores = np.asarray(scores, np.float64)
    bad = np.zeros(n, bool)
    if labels.shape != (n, k) or scores.shape != (n, k):
        return {"bad": n, "score_err": float("inf"),
                "score_err_mean": float("inf"), "rank_gap": float("inf")}
    bad |= (labels < 0).any(1) | (labels >= L).any(1)
    srt = np.sort(labels, axis=1)
    bad |= (srt[:, 1:] == srt[:, :-1]).any(1)
    ok = ~bad
    if not ok.any():
        return {"bad": int(bad.sum()), "score_err": 0.0,
                "score_err_mean": 0.0, "rank_gap": 0.0}
    lab = labels[ok]
    rs = ref.scores[ok].astype(np.float64)
    ra = ref.scale[ok].astype(np.float64)
    tiny = np.finfo(np.float32).tiny
    s_ref = np.take_along_axis(rs, lab, 1)
    a_ref = np.take_along_axis(ra, lab, 1)
    err = np.abs(scores[ok] - s_ref) / np.maximum(a_ref, tiny)
    kth_id = np.argsort(-rs, axis=1, kind="stable")[:, k - 1:k]
    kth = np.take_along_axis(rs, kth_id, 1)
    a_kth = np.take_along_axis(ra, kth_id, 1)
    gap = np.maximum(kth - s_ref, 0.0) / np.maximum(a_ref + a_kth, tiny)
    return {"bad": int(bad.sum()), "score_err": float(err.max()),
            "score_err_mean": float(err.mean()), "rank_gap": float(gap.max())}
