"""Public wrapper for the banded attention kernel: GQA layout, padding,
VMEM budget enforcement, fallback."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.banded_attn import ref
from repro.kernels.banded_attn.kernel import (DEFAULT_QC,
                                              banded_attention_pallas)

VMEM_BUDGET = 14 * 2 ** 20         # leave headroom under 16 MB v5e VMEM


def _vmem_bytes(G: int, qc: int, hd: int, span: int) -> int:
    q = G * qc * hd * 4
    kv = 2 * span * hd * 4
    scores = G * qc * span * 4
    out = G * qc * hd * 4
    return q + kv + scores + out


@partial(jax.jit, static_argnames=("window", "qc", "interpret"))
def banded_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     *, window: int, qc: int = DEFAULT_QC,
                     interpret: bool | None = None) -> jax.Array:
    """Sliding-window attention, (B, Tq, H, hd) x (B, Tk, KV, hd) GQA layout
    (same convention as models/layers.py) -> (B, Tq, H * hd).

    Routes through the Pallas kernel when the band working set fits VMEM,
    else falls back to the jnp oracle (which the XLA-level
    layers.banded_attention already covers in production paths).
    """
    B, Tq, H, hd = q.shape
    Tk, KV = k.shape[1], k.shape[2]
    G = H // KV
    qc = min(qc, Tq)
    while Tq % qc:
        qc //= 2
    span = min(Tk, ((window + qc + 127) // 128) * 128)

    # (B, Tq, H, hd) -> (B*KV, G, Tq, hd); k/v -> (B*KV, Tk, hd)
    q4 = q.reshape(B, Tq, KV, G, hd).transpose(0, 2, 3, 1, 4) \
          .reshape(B * KV, G, Tq, hd)
    k3 = k.transpose(0, 2, 1, 3).reshape(B * KV, Tk, hd)
    v3 = v.transpose(0, 2, 1, 3).reshape(B * KV, Tk, hd)

    if _vmem_bytes(G, qc, hd, span) <= VMEM_BUDGET and span <= Tk:
        out = banded_attention_pallas(q4, k3, v3, window=window, qc=qc,
                                      interpret=interpret)
    else:
        out = ref.banded_attention(q4, k3, v3, window=window)

    # (B*KV, G, Tq, hd) -> (B, Tq, H*hd)
    out = out.reshape(B, KV, G, Tq, hd).transpose(0, 3, 1, 2, 4)
    return out.reshape(B, Tq, H * hd)
