"""Fused squared-hinge objective + gradient + active-mask kernel (TRON
outer-loop hot spot).

Computes, for a shard of labels at once (paper layer-2 parallelism):

    f_l    = ||w_l||^2 + C sum_i max(0, 1 - s_li <w_l, x_i>)^2
    grad_l = 2 w_l + 2C sum_i act_li (<w_l, x_i> - s_li) x_i
    act_li = 1[1 - s_li <w_l, x_i> > 0]        (the label's active set I_l)

The third output is the margin-caching solver protocol's `act_aux`
(core/tron.py): the mask is emitted tile-by-tile from the SAME score
contraction that feeds f/grad, so the TRON/CG loop never runs a separate
(L, D) x (D, N) matmul just to rebuild the active set — the HVP kernel
(kernels/hvp) consumes this mask directly.

Tiling
------
grid = (L/bl, N/bn); j (instances) is the innermost, sequential axis so the
(bl, 1)-objective and (bl, D)-gradient output blocks are *revisited* and
accumulated in VMEM across the N sweep — the margin nonlinearity is applied
tile-by-tile with zero HBM round-trips for the (L, N) score matrix. The
(bl, bn) act tile is written exactly once, at its own (i, j) grid step.
The objective leaves the kernel as an (L, 1) column: a 1-D (bl,) block
does not match the layout XLA gives an f32[L] array on TPU.

VMEM budget (f32, `fused_vmem_bytes`): every block is double-buffered —
    2 x (W (bl, D) + X (bn, D) + grad (bl, D) + S and act (bl, bn))
plus two (bl, D) f32 temporaries (the partial gradient and its sum).
At bl = bn = 128 that is 4096 * D + 256 KB, against the 16 MiB scoped
VMEM limit a v5e kernel gets by default, so D <= 3968 (`MAX_FUSED_D`).
The v5e compiler agrees: at D = 4096 it asks for a 16.38M scoped
allocation against its 16.00M limit and refuses; D = 3968 compiles.
The HVP kernel has the same blocks and shares the bound. ops.py raises
for a larger D rather than computing another way.

MXU notes: both contractions are (128 x D) x (D x 128) and (128 x 128) x
(128 x D) — lane/sublane aligned; f32 accumulation via
preferred_element_type regardless of input dtype.

`interpret=None` auto-selects per backend (compiled Mosaic on TPU, the
interpreter elsewhere — compat.default_pallas_interpret).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.compat import resolve_interpret

DEFAULT_BL = 128      # label-tile rows
DEFAULT_BN = 128      # instance-tile rows
VMEM_LIMIT = 16 * 2**20   # default scoped VMEM of one v5e kernel


def fused_vmem_bytes(D: int, bl: int = DEFAULT_BL,
                     bn: int = DEFAULT_BN) -> int:
    """VMEM one grid step of the hinge (or HVP) kernel needs at width D:
    double-buffered (bl|bn, D) and (bl, bn) blocks plus two (bl, D) f32
    temporaries (see the module docstring)."""
    return 4 * (2 * ((2 * bl + bn) * D + 2 * bl * bn) + 2 * bl * D)


def max_fused_d(bl: int = DEFAULT_BL, bn: int = DEFAULT_BN) -> int:
    """Largest lane-aligned D whose full-width blocks fit `VMEM_LIMIT`."""
    per_d = fused_vmem_bytes(1, bl, bn) - fused_vmem_bytes(0, bl, bn)
    D = (VMEM_LIMIT - fused_vmem_bytes(0, bl, bn)) // per_d
    return D - D % 128


MAX_FUSED_D = max_fused_d()    # 3968 at the default tiles


def _hinge_kernel(w_ref, x_ref, s_ref, f_ref, g_ref, a_ref, *, C: float):
    """One (label-tile i, instance-tile j) grid step."""
    j = pl.program_id(1)
    W = w_ref[...].astype(jnp.float32)       # (bl, D)
    X = x_ref[...].astype(jnp.float32)       # (bn, D)
    S = s_ref[...].astype(jnp.float32)       # (bl, bn)

    scores = jax.lax.dot_general(W, X, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    z = 1.0 - S * scores
    act = (z > 0.0).astype(jnp.float32)
    r = act * (scores - S)                   # = -act * S * z

    f_part = C * jnp.sum(act * z * z, axis=1, keepdims=True)    # (bl, 1)
    g_part = 2.0 * C * jax.lax.dot_general(r, X, (((1,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _init():                             # regularizer terms, once per row-tile
        f_ref[...] = jnp.sum(W * W, axis=1, keepdims=True)
        g_ref[...] = 2.0 * W

    f_ref[...] += f_part
    g_ref[...] += g_part
    a_ref[...] = act                         # (i, j) tile, written once


def hinge_obj_grad_pallas(W: jax.Array, X: jax.Array, S: jax.Array, C: float,
                          *, bl: int = DEFAULT_BL, bn: int = DEFAULT_BN,
                          interpret: bool | None = None):
    """Raw pallas_call -> (f, grad, act). Tile-aligned inputs only (L % bl
    == 0 and N % bn == 0; ops.py pads arbitrary shapes)."""
    L, D = W.shape
    N = X.shape[0]
    assert S.shape == (L, N), (S.shape, (L, N))
    if L % bl != 0 or N % bn != 0:
        raise ValueError(
            f"hinge_obj_grad_pallas needs tile-aligned inputs: got "
            f"(L, N) = {(L, N)} with tiles (bl, bn) = {(bl, bn)}; call "
            "repro.kernels.hinge.ops.objective_grad_act for arbitrary shapes")
    grid = (L // bl, N // bn)
    f, g, act = pl.pallas_call(
        partial(_hinge_kernel, C=C),
        grid=grid,
        in_specs=[pl.BlockSpec((bl, D), lambda i, j: (i, 0)),
                  pl.BlockSpec((bn, D), lambda i, j: (j, 0)),
                  pl.BlockSpec((bl, bn), lambda i, j: (i, j))],
        out_specs=[pl.BlockSpec((bl, 1), lambda i, j: (i, 0)),
                   pl.BlockSpec((bl, D), lambda i, j: (i, 0)),
                   pl.BlockSpec((bl, bn), lambda i, j: (i, j))],
        out_shape=[jax.ShapeDtypeStruct((L, 1), jnp.float32),
                   jax.ShapeDtypeStruct((L, D), jnp.float32),
                   jax.ShapeDtypeStruct((L, N), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(W, X, S)
    return f[:, 0], g, act
