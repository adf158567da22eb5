"""Pallas hinge kernel vs ref.py oracle: shape/dtype sweep (interpret mode)."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels.hinge import ops, ref

SHAPES = [  # (L, N, D) incl. non-multiples of the 128 tiles
    (4, 16, 8),
    (128, 128, 128),
    (130, 100, 64),
    (7, 300, 256),
    (256, 64, 48),
]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("L,N,D", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("C", [0.5, 2.0])
def test_objective_and_grad_allclose(L, N, D, dtype, C):
    rng = np.random.default_rng(L * 31 + N)
    W = jnp.asarray(rng.normal(size=(L, D)) * 0.1).astype(dtype)
    X = jnp.asarray(rng.normal(size=(N, D))).astype(dtype)
    S = jnp.asarray(np.sign(rng.normal(size=(L, N))), jnp.float32)

    f_k, g_k, a_k = ops.objective_grad_act(W, X, S, C, bl=32, bn=32)
    f_r, g_r, a_r = ref.objective_grad_act(W.astype(jnp.float32),
                                           X.astype(jnp.float32), S, C)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(f_k), np.asarray(f_r),
                               rtol=tol, atol=tol * 10)
    np.testing.assert_allclose(np.asarray(g_k), np.asarray(g_r),
                               rtol=tol, atol=tol * 10)
    # The emitted active mask: exactly the (L, N) mask, pad columns/rows
    # sliced away (bf16 scores may flip exact-boundary ties vs the f32
    # oracle; none exist in this random data).
    assert a_k.shape == (L, N)
    np.testing.assert_array_equal(np.asarray(a_k), np.asarray(a_r))


def test_large_d_falls_back_to_ref():
    """D > MAX_FUSED_D has no fallback: both fused wrappers raise, naming
    the bound, instead of computing with the jnp reference."""
    from repro.kernels.hinge.kernel import MAX_FUSED_D
    from repro.kernels.hvp import ops as hvp_ops
    rng = np.random.default_rng(0)
    L, N, D = 4, 8, MAX_FUSED_D + 128
    W = jnp.asarray(rng.normal(size=(L, D)) * 0.01, jnp.float32)
    X = jnp.asarray(rng.normal(size=(N, D)) * 0.1, jnp.float32)
    S = jnp.asarray(np.sign(rng.normal(size=(L, N))), jnp.float32)
    with pytest.raises(ValueError, match=f"={MAX_FUSED_D}"):
        ops.objective_and_grad(W, X, S, 1.0)
    with pytest.raises(ValueError, match=f"={MAX_FUSED_D}"):
        hvp_ops.hessian_vp(W, X, jnp.ones((L, N), jnp.float32), 1.0)
    # At the bound itself the kernels run and match the reference.
    W, X = W[:, :MAX_FUSED_D], X[:, :MAX_FUSED_D]
    f_k, g_k = ops.objective_and_grad(W, X, S, 1.0)
    f_r, g_r = ref.objective_and_grad(W, X, S, 1.0)
    np.testing.assert_allclose(np.asarray(f_k), np.asarray(f_r),
                               rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(g_k), np.asarray(g_r),
                               rtol=1e-4, atol=1e-3)


def test_pad_instance_correction_exact():
    """The analytic pad-row correction must be exact: N=1 with bn=32 pads 31
    instances; objective must match the unpadded reference to fp precision."""
    rng = np.random.default_rng(1)
    L, N, D = 8, 1, 32
    W = jnp.asarray(rng.normal(size=(L, D)), jnp.float32)
    X = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
    S = jnp.asarray(np.sign(rng.normal(size=(L, N))), jnp.float32)
    f_k, _ = ops.objective_and_grad(W, X, S, 3.0, bl=8, bn=32)
    f_r, _ = ref.objective_and_grad(W, X, S, 3.0)
    np.testing.assert_allclose(np.asarray(f_k), np.asarray(f_r),
                               rtol=1e-5, atol=1e-4)
