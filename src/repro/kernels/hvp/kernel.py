"""Fused generalized-Hessian vector product kernel (CG inner-loop hot spot).

    Hv_l = 2 v_l + 2C X^T (act_l * (X v_l))

This runs once per CG iteration per Newton step — by far the most-executed
compute in DiSMEC training. Same (L/bl, N/bn) accumulation tiling as the
hinge kernel, and the same blocks, so the same VMEM bound
(`kernels/hinge/kernel.py`, `max_fused_d`): the (bl, bn) masked
intermediate act * (X v) lives only in VMEM.

`act` is the active-set payload the fused hinge kernel emitted at the
current Newton iterate (the margin-caching protocol, core/tron.py) — this
kernel performs ONE score-shaped contraction (X v) per call; the mask is
never re-derived.

`interpret=None` auto-selects per backend (compiled Mosaic on TPU, the
interpreter elsewhere — compat.default_pallas_interpret).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.compat import resolve_interpret
from repro.kernels.hinge.kernel import DEFAULT_BL, DEFAULT_BN


def _hvp_kernel(v_ref, x_ref, a_ref, o_ref, *, C: float):
    j = pl.program_id(1)
    V = v_ref[...].astype(jnp.float32)       # (bl, D)
    X = x_ref[...].astype(jnp.float32)       # (bn, D)
    A = a_ref[...].astype(jnp.float32)       # (bl, bn) active mask

    Xv = jax.lax.dot_general(V, X, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (bl, bn)
    part = 2.0 * C * jax.lax.dot_general(A * Xv, X, (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = 2.0 * V

    o_ref[...] += part


def hvp_pallas(V: jax.Array, X: jax.Array, act: jax.Array, C: float,
               *, bl: int = DEFAULT_BL, bn: int = DEFAULT_BN,
               interpret: bool | None = None) -> jax.Array:
    """Raw pallas_call. Tile-aligned inputs only (L % bl == 0 and
    N % bn == 0; ops.py pads arbitrary shapes)."""
    L, D = V.shape
    N = X.shape[0]
    assert act.shape == (L, N), (act.shape, (L, N))
    if L % bl != 0 or N % bn != 0:
        raise ValueError(
            f"hvp_pallas needs tile-aligned inputs: got (L, N) = {(L, N)} "
            f"with tiles (bl, bn) = {(bl, bn)}; call "
            "repro.kernels.hvp.ops.hessian_vp for arbitrary shapes")
    grid = (L // bl, N // bn)
    return pl.pallas_call(
        partial(_hvp_kernel, C=C),
        grid=grid,
        in_specs=[pl.BlockSpec((bl, D), lambda i, j: (i, 0)),
                  pl.BlockSpec((bn, D), lambda i, j: (j, 0)),
                  pl.BlockSpec((bl, bn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bl, D), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((L, D), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(V, X, act)
