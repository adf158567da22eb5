"""Pallas TPU kernels for DiSMEC's compute hot-spots.

Each kernel directory contains:
  kernel.py — pl.pallas_call body + BlockSpec tiling (TPU target)
  ops.py    — jit'd public wrapper: padding and shape/VMEM-bound checks
  ref.py    — pure-jnp oracle the tests assert against

Kernels (DESIGN.md §3):
  hinge       fused squared-hinge objective + gradient + active mask
              (TRON outer loop; the mask output feeds the margin-caching
              solver protocol, core/tron.py)
  hvp         fused generalized-Hessian vector product consuming the
              cached mask (CG inner loop)
  bsr_predict block-sparse W x predict — skips Delta-pruned zero blocks;
              its top-k is `jax.lax.top_k` over the (n, L) scores

Every kernel takes `interpret=None` and auto-selects per backend
(compiled Mosaic on TPU, the interpreter elsewhere —
compat.default_pallas_interpret). The CPU tests check each kernel against
its oracle in interpret mode; tests/kernels/test_tpu_compile.py compiles
each for a described v5e chip, which enforces the (8, 128) block tiling
and the VMEM budgets documented per kernel.
"""
