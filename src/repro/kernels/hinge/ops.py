"""Public wrapper for the fused hinge kernel: padding and the D bound."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.hinge.kernel import hinge_obj_grad_pallas, max_fused_d


def _pad_to(x: jax.Array, axis: int, mult: int, value: float = 0.0):
    n = x.shape[axis]
    p = (-n) % mult
    if p == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, p)
    return jnp.pad(x, pad, constant_values=value)


def check_fused_d(D: int, bl: int, bn: int) -> None:
    """Raise when full-width (bl|bn, D) blocks cannot fit VMEM."""
    bound = max_fused_d(bl, bn)
    if D > bound:
        raise ValueError(
            f"feature dim D={D} exceeds the fused-kernel bound "
            f"max_fused_d(bl={bl}, bn={bn})={bound}: full-width blocks "
            "would not fit VMEM; use SolverSpec(ops='jnp') at this width")


@partial(jax.jit, static_argnames=("C", "bl", "bn", "interpret"))
def objective_grad_act(W: jax.Array, X: jax.Array, S: jax.Array, C: float,
                       *, bl: int = 128, bn: int = 128,
                       interpret: bool | None = None,
                       ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused (objective, gradient, active mask) for all labels; pads L and N
    to tile multiples. Padded instances get sign -1 and x = 0 => margin
    z = 1 - 0 = 1 > 0: active regardless of sign, so each pad row adds a
    constant C to every label's objective — subtracted back analytically —
    while its gradient contribution is exactly 0 (r x = 0). Padded label
    rows (W = 0, S = -1) and padded mask rows/columns are sliced away: the
    returned act is the true (L, N) mask, directly consumable by the HVP
    kernel (whose wrapper re-pads with zeros — a zero-mask instance
    contributes nothing).
    """
    L, D = W.shape
    N = X.shape[0]
    check_fused_d(D, bl, bn)

    Wp = _pad_to(W, 0, bl)
    Xp = _pad_to(X, 0, bn)
    Sp = _pad_to(_pad_to(S, 0, bl, -1.0), 1, bn, -1.0)
    n_pad_inst = Xp.shape[0] - N

    f, g, act = hinge_obj_grad_pallas(Wp, Xp, Sp, C, bl=bl, bn=bn,
                                      interpret=interpret)
    # Each padded instance (x = 0, s = -1) is active with z = 1 for every
    # label: remove its constant C contribution from the objective.
    f = f[:L] - C * n_pad_inst
    return f, g[:L], act[:L, :N]


@partial(jax.jit, static_argnames=("C", "bl", "bn", "interpret"))
def objective_and_grad(W: jax.Array, X: jax.Array, S: jax.Array, C: float,
                       *, bl: int = 128, bn: int = 128,
                       interpret: bool | None = None,
                       ) -> tuple[jax.Array, jax.Array]:
    """(f, grad) only — see `objective_grad_act` for the solver-facing form
    that also emits the active mask from the same score pass."""
    f, g, _ = objective_grad_act(W, X, S, C, bl=bl, bn=bn,
                                 interpret=interpret)
    return f, g
