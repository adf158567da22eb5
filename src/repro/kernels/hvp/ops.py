"""Public wrapper for the Hessian-vector-product kernel: padding and the D
bound — the same arbitrary-shape contract as the hinge wrapper, so the
Pallas training path works on any (L, N, D) instead of silently requiring
tile-aligned inputs (the raw `hvp_pallas` rejects those loudly)."""

from __future__ import annotations

from functools import partial

import jax

from repro.kernels.hinge.ops import _pad_to, check_fused_d
from repro.kernels.hvp.kernel import hvp_pallas


@partial(jax.jit, static_argnames=("C", "bl", "bn", "interpret"))
def hessian_vp(V: jax.Array, X: jax.Array, act: jax.Array, C: float,
               *, bl: int = 128, bn: int = 128,
               interpret: bool | None = None) -> jax.Array:
    """Hv for all labels, any (L, N, D): pads every axis to its tile
    multiple. Padded instances have x = 0 and act = 0, so their contribution
    is exactly zero; padded label rows are sliced away. `act` is the cached
    mask from the hinge kernel's `objective_grad_act` (or any (L, N) float
    mask)."""
    L, D = V.shape
    N = X.shape[0]
    if act.shape != (L, N):
        raise ValueError(
            f"act must be the (L, N) = {(L, N)} active mask matching V/X; "
            f"got {act.shape} — pass the mask emitted by "
            "kernels.hinge.ops.objective_grad_act at the same iterate")
    check_fused_d(D, bl, bn)
    Vp = _pad_to(V, 0, bl)
    Xp = _pad_to(X, 0, bn)
    Ap = _pad_to(_pad_to(act, 0, bl), 1, bn)
    out = hvp_pallas(Vp, Xp, Ap, C, bl=bl, bn=bn, interpret=interpret)
    return out[:L]
