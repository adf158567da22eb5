"""Compile (never run) every main-path Pallas kernel for a TPU v5e chip.

Interpret mode accepts block shapes and VMEM budgets the chip's compiler
refuses, so the serving and training kernels are compiled here at
Wiki10-31K width (D = 102,016 after block padding, 128 x 128 blocks)
against a described `v5e:2x2` topology. The TPU compiler ships with
libtpu; no chip is needed.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load libtpu, and a module that touched it
while being collected would give pytest-xdist workers different tests.
"""

import os

import pytest

import jax
import jax.numpy as jnp

from repro.kernels.bsr_predict import kernel as bsr
from repro.kernels.hinge.kernel import MAX_FUSED_D, hinge_obj_grad_pallas
from repro.kernels.hvp.kernel import hvp_pallas

DP = 102016           # Wiki10-31K's D = 101,938 padded to 128-wide blocks
BL = BD = 128
LP = 4096             # padded label rows: 32 row blocks
NB = 8192             # packed blocks, a ~32% block density
MAX_PER_ROW = DP // BD
SEL = 4               # shortlist width in row blocks


@pytest.fixture(scope="module")
def chip():
    """A single-device sharding on the first chip of a described v5e:2x2,
    with the persistent compile cache off (its entries could not be read
    back without a chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        cache_was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            jax.config.update("jax_enable_compilation_cache", cache_was_on)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", cache_was_on)


def _compile(chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text       # the Mosaic kernel is in there


F32, I32, I8 = jnp.float32, jnp.int32, jnp.int8


@pytest.mark.parametrize("n", [1, 16])
@pytest.mark.parametrize("dtype", ["fp32", "int8"])
def test_bsr_exhaustive(chip, n, dtype):
    meta = [((NB,), I32), ((NB,), I32)]
    if dtype == "fp32":
        _compile(chip, lambda x, b, r, c: bsr.bsr_predict_pallas(
            x, b, r, c, LP // BL, interpret=False),
            ((n, DP), F32), ((NB, BL, BD), F32), *meta)
    else:
        _compile(chip, lambda x, b, s, r, c: bsr.bsr_predict_int8_pallas(
            x, b, s, r, c, LP // BL, interpret=False),
            ((n, DP), F32), ((NB, BL, BD), I8), ((NB,), F32), *meta)


@pytest.mark.parametrize("n", [1, 16])
@pytest.mark.parametrize("dtype", ["fp32", "int8"])
def test_bsr_gather(chip, n, dtype):
    tail = [((NB,), I32), ((LP // BL + 1,), I32), ((SEL,), I32)]
    if dtype == "fp32":
        _compile(chip, lambda x, b, c, p, s: bsr.bsr_predict_gather_pallas(
            x, b, c, p, s, MAX_PER_ROW, interpret=False),
            ((n, DP), F32), ((NB, BL, BD), F32), *tail)
    else:
        _compile(chip, lambda x, b, sc, c, p, s:
                 bsr.bsr_predict_gather_int8_pallas(
                     x, b, sc, c, p, s, MAX_PER_ROW, interpret=False),
                 ((n, DP), F32), ((NB, BL, BD), I8), ((NB,), F32), *tail)


@pytest.mark.parametrize("n", [1, 16])
@pytest.mark.parametrize("dtype", ["fp32", "int8"])
def test_bsr_gather_pq(chip, n, dtype):
    tail = [((NB,), I32), ((LP // BL + 1,), I32), ((n, SEL), I32)]
    if dtype == "fp32":
        _compile(chip, lambda x, b, c, p, s:
                 bsr.bsr_predict_gather_pq_pallas(
                     x, b, c, p, s, MAX_PER_ROW, interpret=False),
                 ((n, DP), F32), ((NB, BL, BD), F32), *tail)
    else:
        _compile(chip, lambda x, b, sc, c, p, s:
                 bsr.bsr_predict_gather_pq_int8_pallas(
                     x, b, sc, c, p, s, MAX_PER_ROW, interpret=False),
                 ((n, DP), F32), ((NB, BL, BD), I8), ((NB,), F32), *tail)


def test_hinge_at_max_fused_d(chip):
    D = MAX_FUSED_D
    _compile(chip, lambda W, X, S: hinge_obj_grad_pallas(
        W, X, S, 1.0, interpret=False),
        ((256, D), F32), ((256, D), F32), ((256, 256), F32))


def test_hvp_at_max_fused_d(chip):
    D = MAX_FUSED_D
    _compile(chip, lambda V, X, A: hvp_pallas(V, X, A, 1.0, interpret=False),
             ((256, D), F32), ((256, D), F32), ((256, 256), F32))
