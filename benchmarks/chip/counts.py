"""Operations and bytes the work needs, from shapes alone, and the peaks.

These are the benchmark's own copies: the program may change how it
computes, but not what the yardstick says the work is.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The peak table's row for this device kind; an unknown kind is an
    error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"(known: {sorted(table)})")
    return table[device_kind]


# -- serving: the exhaustive BSR predict + top-k ------------------------------

def bsr_flops(n_blocks: int, block_shape, n: int) -> int:
    """2 * n * bl * bd per stored block: the multiply-adds the block
    product needs (kernels/bsr_predict/ops.py `model_flops`)."""
    bl, bd = block_shape
    return 2 * n * bl * bd * n_blocks


def bsr_min_bytes(n_blocks: int, block_shape, Lp: int, Dp: int, n: int,
                  value_bytes: int = 4) -> int:
    """Least HBM traffic of one predict: every stored block read once, the
    (n, Dp) requests read once, the (n, Lp) fp32 scores written once.
    (kernels/bsr_predict/ops.py `predict_bytes` also counts x once per row
    block, which is how that kernel happens to move it, not what the
    product needs.)"""
    bl, bd = block_shape
    return value_bytes * n_blocks * bl * bd + 4 * n * Dp + 4 * n * Lp


def least_time_s(flops: float, bytes_: float, peak: dict) -> float:
    """The larger of operations over the bf16 peak and bytes over HBM
    bandwidth: the least time the chip could take."""
    return max(flops / peak["bf16_flops_per_s"],
               bytes_ / peak["hbm_bytes_per_s"])

