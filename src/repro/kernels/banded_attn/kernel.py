"""Pallas banded sliding-window attention — the backbone hot spot that the
SSPerf hillclimb moved from O(T^2) masking to O(T * (w + qc)) band slicing
(EXPERIMENTS.md, hymba iteration 2), here as an explicit TPU kernel.

Tiling
------
grid = (B * KV, nq): one step per (batch x kv-head group, query block).
The query block (G, qc, hd) lives in VMEM via BlockSpec; K/V stay UNBLOCKED
(memory_space ANY -> HBM on TPU) and the kernel reads exactly the
[band_start, band_start + span) rows it attends to — the DMA the XLA-level
implementation relies on the compiler to find, made explicit.

Band geometry: span = window + qc rounded up to a lane multiple; the start
is clamped so the slice never leaves [0, Tk]. Causal + window masking is
applied from absolute positions inside the kernel.

VMEM budget per step (f32): q (G, qc, hd) + band K/V 2*(span, hd) + scores
(G*qc, span). hymba prefill (G=5, qc=256, hd=64, w=1024, span=1280):
0.3 MB + 0.7 MB + 6.5 MB ~= 7.5 MB < 16 MB v5e VMEM. ops.py asserts this.

MXU: scores (G*qc, hd) x (hd, span) and (G*qc, span) x (span, hd) — both
lane-aligned for hd, span multiples of 128.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.compat import resolve_interpret

DEFAULT_QC = 256
NEG_INF = float(-3.0e38)


def _banded_kernel(q_ref, k_ref, v_ref, o_ref, *, window: int, span: int,
                   qc: int, Tk: int, scale: float):
    """One (batch*kv-head, q-block) step."""
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)                   # (G, qc, hd)
    G, _, hd = q.shape

    q_end = (qi + 1) * qc
    start = jnp.clip(q_end - span, 0, Tk - span)
    k = k_ref[0, pl.ds(start, span), :].astype(jnp.float32)   # (span, hd)
    v = v_ref[0, pl.ds(start, span), :].astype(jnp.float32)

    qf = q.reshape(G * qc, hd)
    s = jax.lax.dot_general(qf, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    # Rows are (g, q) flattened g-major; the position is the q component.
    row_q = (jax.lax.broadcasted_iota(jnp.int32, (G * qc, span), 0) % qc) \
        + qi * qc
    col_k = start + jax.lax.broadcasted_iota(jnp.int32, (G * qc, span), 1)
    mask = (col_k <= row_q) & (col_k > row_q - window)
    s = jnp.where(mask, s, NEG_INF)

    p = jax.nn.softmax(s, axis=-1)
    out = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    o_ref[0] = out.reshape(G, qc, hd).astype(o_ref.dtype)


def banded_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                            *, window: int, qc: int = DEFAULT_QC,
                            interpret: bool | None = None) -> jax.Array:
    """q (BKV, G, Tq, hd), k/v (BKV, Tk, hd) -> (BKV, G, Tq, hd).

    Requires Tq % qc == 0 and span <= Tk (ops.py pads/validates).
    """
    BKV, G, Tq, hd = q.shape
    Tk = k.shape[1]
    assert Tq % qc == 0
    nq = Tq // qc
    # Lane-align the band span.
    span = min(Tk, ((window + qc + 127) // 128) * 128)
    scale = 1.0 / math.sqrt(hd)

    return pl.pallas_call(
        partial(_banded_kernel, window=window, span=span, qc=qc, Tk=Tk,
                scale=scale),
        grid=(BKV, nq),
        in_specs=[
            pl.BlockSpec((1, G, qc, hd), lambda b, i: (b, 0, i, 0)),
            pl.BlockSpec((1, Tk, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, Tk, hd), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, qc, hd), lambda b, i: (b, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BKV, G, Tq, hd), q.dtype),
        interpret=resolve_interpret(interpret),
    )(q, k, v)
