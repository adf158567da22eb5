"""The plain reference of `reference.py`, streamed over label row blocks.

At a million labels the full reference's two (n, L) arrays, and the
argsort of them, no longer fit a check. This copy makes one pass over W's
row blocks, regenerated from the seed (`gen.row_block`), in float32 at the
highest matmul precision, and keeps per sampled row only what the
comparison reads: the reference's k best labels and scores (ties to the
lower label id, as the full reference's stable sort), the scale
sum_j |x_j w_lj| of its k-th, and the score and scale of each served
label. It never holds (n, L). `judge_answers` then computes the numbers of
`reference.judge_answers` from those readings, the same to the last bit.

Nothing here imports the program (`repro`) or takes anything it made.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

import gen
import reference


class Pending(NamedTuple):
    """The rows to judge; the pass runs once the served labels are
    known."""
    cfg: dict
    seed: int
    x: np.ndarray


class Readings(NamedTuple):
    """Per row: the reference's k best (ids, scores), the scale of its
    k-th, and the score and scale of each served label, each (n, k)."""
    top_ids: np.ndarray
    top_scores: np.ndarray
    kth_scale: np.ndarray
    served_scores: np.ndarray
    served_scales: np.ndarray


@functools.partial(jax.jit, static_argnames=("g", "sigma", "delta", "k"))
def _pass(key, x, served, *, g, sigma, delta, k):
    hi = jax.lax.Precision.HIGHEST
    n, bl = x.shape[0], g["bl"]

    def step(carry, i):
        top_s, top_i, top_a, s_at, a_at = carry
        blk = gen.row_block(key, i, g, sigma, delta)        # (ncb, bl, bd)
        w = blk.transpose(1, 0, 2).reshape(bl, g["Dp"])
        s = jnp.dot(x, w.T, precision=hi)                   # (n, bl)
        a = jnp.dot(jnp.abs(x), jnp.abs(w).T, precision=hi)
        ids = i * bl + jnp.arange(bl, dtype=jnp.int32)
        real = jnp.broadcast_to(ids < g["L"], s.shape)
        cat_s = jnp.concatenate([top_s, jnp.where(real, s, -jnp.inf)], 1)
        cat_i = jnp.concatenate([top_i, jnp.broadcast_to(ids, s.shape)], 1)
        cat_a = jnp.concatenate([top_a, a], 1)
        top_s, at = jax.lax.top_k(cat_s, k)
        top_i = jnp.take_along_axis(cat_i, at, 1)
        top_a = jnp.take_along_axis(cat_a, at, 1)
        here = served // bl == i
        col = served % bl
        s_at = jnp.where(here, jnp.take_along_axis(s, col, 1), s_at)
        a_at = jnp.where(here, jnp.take_along_axis(a, col, 1), a_at)
        return (top_s, top_i, top_a, s_at, a_at), None

    zeros = jnp.zeros((n, k), jnp.float32)
    init = (jnp.full((n, k), -jnp.inf, jnp.float32),
            jnp.full((n, k), -1, jnp.int32), zeros, zeros, zeros)
    out, _ = jax.lax.scan(step, init, jnp.arange(g["nrb"]))
    return out


def readings(cfg: dict, seed: int, x: np.ndarray, served: np.ndarray,
             k: int, *, chunk: int = 256) -> Readings:
    """One streamed pass for rows x (n, D) and their served label ids
    (n, k), every id in [0, L)."""
    g = gen.bsr_geometry(cfg)
    w = cfg["weights"]
    key = gen.weights_key(seed)
    xs = np.zeros((len(x), g["Dp"]), np.float32)
    xs[:, :g["D"]] = x
    served = np.asarray(served, np.int32)
    parts = []
    for s in range(0, len(xs), chunk):
        out = _pass(key, jnp.asarray(xs[s:s + chunk]),
                    jnp.asarray(served[s:s + chunk]), g=reference._Frozen(g),
                    sigma=float(w["sigma"]), delta=float(w["delta"]), k=k)
        parts.append([np.asarray(o) for o in out])
    top_s, top_i, top_a, s_at, a_at = (np.concatenate(p)
                                       for p in zip(*parts))
    return Readings(top_i, top_s, top_a[:, k - 1], s_at, a_at)


def serve_reference(cfg: dict, seed: int, x: np.ndarray) -> Pending:
    """The rows x (n, D) to judge; `judge_answers` runs the pass."""
    return Pending(cfg, seed, np.asarray(x, np.float32))


def judge_answers(labels, scores, ref: Pending, k: int) -> dict:
    """`reference.judge_answers`'s numbers for served (labels, scores) of
    shape (n, k) against the streamed readings of the same rows."""
    n, L = len(ref.x), ref.cfg["n_labels"]
    labels = np.asarray(labels)
    scores = np.asarray(scores, np.float64)
    if labels.shape != (n, k) or scores.shape != (n, k):
        return {"bad": n, "score_err": float("inf"),
                "score_err_mean": float("inf"), "rank_gap": float("inf")}
    srt = np.sort(labels, axis=1)
    bad = ((labels < 0).any(1) | (labels >= L).any(1)
           | (srt[:, 1:] == srt[:, :-1]).any(1))
    ok = ~bad
    if not ok.any():
        return {"bad": int(bad.sum()), "score_err": 0.0,
                "score_err_mean": 0.0, "rank_gap": 0.0}
    r = readings(ref.cfg, ref.seed, ref.x[ok], labels[ok], k)
    tiny = np.finfo(np.float32).tiny
    s_ref = r.served_scores.astype(np.float64)
    a_ref = r.served_scales.astype(np.float64)
    kth = r.top_scores[:, k - 1:k].astype(np.float64)
    a_kth = r.kth_scale[:, None].astype(np.float64)
    err = np.abs(scores[ok] - s_ref) / np.maximum(a_ref, tiny)
    gap = np.maximum(kth - s_ref, 0.0) / np.maximum(a_ref + a_kth, tiny)
    return {"bad": int(bad.sum()), "score_err": float(err.max()),
            "score_err_mean": float(err.mean()), "rank_gap": float(gap.max())}
