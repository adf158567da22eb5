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
import numpy as np

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
        _compile(chip, lambda x, b, r, c, p: bsr.bsr_predict_pallas(
            x, b, r, c, LP // BL, row_ptr=p, interpret=False),
            ((n, DP), F32), ((NB, BL, BD), F32), *meta,
            ((LP // BL + 1,), I32))
    else:
        _compile(chip, lambda x, b, s, r, c: bsr.bsr_predict_int8_pallas(
            x, b, s, r, c, LP // BL, interpret=False),
            ((n, DP), F32), ((NB, BL, BD), I8), ((NB,), F32), *meta)


# The wiki10-31k-half serving cell's model (121 x 797 packed blocks) and
# full Wiki10-31K's (242 x 797).
HALF_NB, HALF_LP = 96437, 15488
FULL_NB, FULL_LP = 192874, 30976


@pytest.mark.parametrize("nb,lp,n", [
    (HALF_NB, HALF_LP, 8), (HALF_NB, HALF_LP, 32),
    (HALF_NB, HALF_LP, 128),
    (HALF_NB, HALF_LP, 208),             # the widest launch in one row tile
    (HALF_NB, HALF_LP, 256),             # two row tiles of 128
    (FULL_NB, FULL_LP, 32), (FULL_NB, FULL_LP, 128),
    (1, LP, 16),                         # the fully pruned sentinel model
    (20, LP, 16),                        # under one group's 16 blocks twice
    (1000, LP, 16)])                     # under one SMEM window
def test_bsr_exhaustive_wiki10(chip, nb, lp, n):
    """The exhaustive fp32 kernel at Wiki10-31K width: the VMEM limit a row
    tile asks for must be granted, and the block coordinates' SMEM windows
    and the 16-block W window must compile however few blocks there are."""
    _compile(chip, lambda x, b, r, c, p: bsr.bsr_predict_pallas(
        x, b, r, c, lp // BL, row_ptr=p, interpret=False),
        ((n, DP), F32), ((nb, BL, BD), F32), ((nb,), I32), ((nb,), I32),
        ((lp // BL + 1,), I32))


# LF-AmazonTitles-1.3M: 10,198 x 6 packed blocks, Lp 1,305,344, Dp 768.
AMAZON_NRB, AMAZON_NCB = 10198, 6


@pytest.mark.parametrize("n", [1, 16, 32, 256])
def test_bsr_exhaustive_lf_amazontitles(chip, n):
    """The exhaustive fp32 kernel at a million labels over 768 features:
    up to 16 rows the scores stay resident in VMEM (83.6 MB at 16), wider
    launches stream them with x resident, and both must compile."""
    nb = AMAZON_NRB * AMAZON_NCB
    assert bsr.streams_scores(n, AMAZON_NRB * BL, AMAZON_NCB * BD, BL) == \
        (n > 16)
    _compile(chip, lambda x, b, r, c, p: bsr.bsr_predict_pallas(
        x, b, r, c, AMAZON_NRB, row_ptr=p, interpret=False),
        ((n, AMAZON_NCB * BD), F32), ((nb, BL, BD), F32), ((nb,), I32),
        ((nb,), I32), ((AMAZON_NRB + 1,), I32))


@pytest.mark.parametrize("n", [4, 64, 256])
def test_bsr_topk_step_lf_amazontitles(chip, n):
    """The serving step at LF-AmazonTitles-1.3M (`serve/xmc.py::_bsr_topk`,
    the grouped top-k): the kernel is its only Pallas call, no other
    instruction writes an array as large as the (n, 1,305,344) scores, so
    the max pass and the candidates' gather read the kernel's output in
    place, and no gather is lowered to a loop over its slices."""
    import functools
    import re

    from repro.core.pruning import BlockSparseModel
    from repro.kernels.bsr_predict import ops
    from repro.serve.xmc import _bsr_topk

    nrb, ncb = AMAZON_NRB, AMAZON_NCB
    lp, dp, nb = nrb * BL, ncb * BD, nrb * ncb
    assert ops.topk_grouped(lp, 5)
    step = functools.partial(_bsr_topk, k=5, n_labels=1305265,
                             interpret=False)
    x, blocks, rows, cols, ptr = [
        jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in [
            ((n, dp), F32), ((nb, BL, BD), F32), ((nb,), I32), ((nb,), I32),
            ((nrb + 1,), I32)]]
    model = BlockSparseModel(blocks=blocks, block_rows=rows, block_cols=cols,
                             row_ptr=ptr, shape=(lp, dp),
                             block_shape=(BL, BD), orig_shape=(1305265, dp))
    text = jax.jit(step).lower(x, model).compile().as_text()
    entry = text[text.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    assert entry.count('custom_call_target="tpu_custom_call"') == 1
    assert " while(" not in entry
    wide = []
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* (\S+?)\(", line)
        if m and m.group(2) not in ("parameter", "custom-call", "bitcast"):
            size = np.prod([int(d) for d in m.group(1).split(",") if d])
            if size >= n * lp:
                wide.append(line.strip()[:120])
    assert not wide


def _gather_args(n, dtype, sel):
    """Shapes of (x, blocks, block_cols, row_ptr, sel, scales) at Wiki10-31K
    width; no scales for fp32 blocks."""
    blocks = ((NB, BL, BD), F32 if dtype == "fp32" else I8)
    scales = [] if dtype == "fp32" else [((NB,), F32)]
    return [((n, DP), F32), blocks, ((NB,), I32), ((LP // BL + 1,), I32),
            (sel, I32), *scales]


@pytest.mark.parametrize("n", [1, 16])
@pytest.mark.parametrize("dtype", ["fp32", "int8"])
def test_bsr_gather(chip, n, dtype):
    _compile(chip, lambda x, b, c, p, s, sc=None:
             bsr.bsr_predict_gather_pallas(
                 x, b, c, p, s, MAX_PER_ROW, scales=sc, interpret=False),
             *_gather_args(n, dtype, (SEL,)))


@pytest.mark.parametrize("n", [1, 16])
@pytest.mark.parametrize("dtype", ["fp32", "int8"])
def test_bsr_gather_pq(chip, n, dtype):
    _compile(chip, lambda x, b, c, p, s, sc=None:
             bsr.bsr_predict_gather_pq_pallas(
                 x, b, c, p, s, MAX_PER_ROW, scales=sc, interpret=False),
             *_gather_args(n, dtype, (n, SEL)))


def test_hinge_at_max_fused_d(chip):
    D = MAX_FUSED_D
    _compile(chip, lambda W, X, S: hinge_obj_grad_pallas(
        W, X, S, 1.0, interpret=False),
        ((256, D), F32), ((256, D), F32), ((256, 256), F32))


def test_hvp_at_max_fused_d(chip):
    D = MAX_FUSED_D
    _compile(chip, lambda V, X, A: hvp_pallas(V, X, A, 1.0, interpret=False),
             ((256, D), F32), ((256, D), F32), ((256, 256), F32))
