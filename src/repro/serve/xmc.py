"""XMC serving engine: top-k label queries over a pruned DiSMEC model.

This is the paper's distributed prediction (§2.2.1) as a serving subsystem
rather than an example script. In the declarative session API it is the
back half of the one experiment object: a `ServeSpec` (backend kind, k,
buckets, Pallas mode) rides inside every checkpoint manifest, and

    from repro.xmc_api import CheckpointHandle
    engine = CheckpointHandle.open(ckpt_dir).engine()

builds this engine exactly as the spec describes (pass
`engine(serve_override=ServeSpec(...))` to serve the same weights
differently). Backends live in a decorator registry —
`@register_backend("kind")` plugs a new scoring implementation (quantized,
multi-model, ...) into the engine, `make_backend` is a thin lookup, and
`ServeSpec(backend="kind")` selects it without touching engine code.

One engine, five built-in interchangeable backends behind the
`PredictBackend` protocol:

  dense     — jitted X @ W.T + lax.top_k on the densified model. Baseline
              and reference semantics.
  bsr       — the block-sparse Pallas predict kernel, then the top-k
              (kernels/bsr_predict.ops.bsr_predict_topk); the
              model stays in packed BSR form end-to-end, compute scales
              with block density.
  sharded   — label-sharded local-topk + all-gather merge
              (core.prediction.predict_topk_sharded) on a device mesh; only
              k*n_shards candidates ever cross the interconnect.
  shortlist — two-stage sub-linear scoring: a coarse stage
              (serve/shortlist.py — block centroids, a learned one-vs-rest
              meta-classifier, or a fastxml-style routing tree, whichever
              the checkpoint's artifact holds) picks the top-B BSR row
              blocks, then the gathered-block Pallas kernel
              (bsr_predict_gather_topk) scores only those blocks. Compute
              scales with B * block_size + R * D, not L * D. Falls back to
              exhaustive BSR when the checkpoint has no shortlist artifact.
              `ShortlistBackend(int8=True)` gives the fine stage the int8
              model — coarse gate AND quarter weight traffic.
              `per_query=True` selects top-B blocks per QUERY and scores
              each row's own list through the ragged-gather kernel
              (bsr_predict_gather_pq_topk); B = n_row_blocks collapses back
              to the shared exhaustive-equivalent path.
  int8      — the bsr path over the symmetric per-block int8 artifact
              (`core.pruning.Int8BlockSparseModel`): int8 tiles + fp32
              per-block scales dequantized in-register, ~0.25x the weight
              HBM traffic of fp32 BSR at scores within the per-block
              quantization bound (so top-k agreement, not bit equality).

All built-ins except int8 produce identical top-k label ids on the same
pruned model
(the shortlist backend whenever its candidate set covers the true top-k;
exactly, tie order included, when B equals the row-block count): padding
labels a backend introduces (BSR block padding, shard divisibility padding)
are masked below any real score before the merge, and fully pruned real
labels keep their exact-zero dense score in every backend.

Request-side machinery lives here too: the engine pulls requests through
`serve.batching.MicroBatchQueue` (size-bucketed padding of ragged streams),
warms up one XLA compile per bucket, and tracks per-request latency
percentiles (enqueue -> completion, so queue wait is measured). The
synchronous path is `submit()` + `step()`; `engine.server()` wraps the
same engine in the async continuous-batching loop (`serve/server.py`) —
future-style results, deadline-launched buckets, admission control —
without changing the backend math or the top-k bits. Backend math lives
in module-level jitted functions that take the packed model whole, so two
backends over equal-shaped models share one XLA compile cache entry per
bucket — opening a second engine never repeats the first one's warm-up
compiles (the process-wide ledger below skips the redundant dispatches).
Models load from the sparse checkpoint artifact written by
`BlockSparseModel.save` — saved once offline like the paper's per-batch
model files, served without re-densifying (the dense/sharded backends
densify in memory at load; the checkpoint on disk is always sparse).
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import inspect
import time
from typing import Iterable, Protocol, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import resolve_interpret
from repro.core.prediction import predict_topk_sharded
from repro.core.pruning import (BlockSparseModel, Int8BlockSparseModel,
                                quantize_block_sparse, to_block_sparse)
from repro.kernels.bsr_predict import kernel as bsr_kernel
from repro.serve.batching import (DEFAULT_BUCKETS, LatencyStats,
                                  MicroBatchQueue)
from repro.serve.shortlist import ShortlistArtifact, build_shortlist

Array = jax.Array

#: Built-in backend kinds (the registry below may grow beyond these).
BACKENDS = ("dense", "bsr", "sharded", "shortlist", "int8")


class PredictBackend(Protocol):
    """What the engine needs from a scoring implementation."""

    name: str
    n_labels: int
    k: int

    def topk(self, x: Array) -> tuple[Array, Array]:
        """x (n, D) -> (scores, label ids), each (n, k)."""
        ...


# ---------------------------------------------------------------------------
# Module-level jitted scoring functions. A packed model is a pytree
# (core/pruning.py) and goes in whole: jax keys the compile cache on its
# arrays' shapes and dtypes and its static shapes, so any two backends with
# equal (D, k) and model geometry share one executable per bucket, and an
# fp32 model and its int8 twin never do.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k",))
def _dense_topk(x: Array, W: Array, k: int) -> tuple[Array, Array]:
    return jax.lax.top_k(x @ W.T, k)


@functools.partial(jax.jit, static_argnames=("k", "n_labels", "interpret"))
def _bsr_topk(x, model, *, k, n_labels, interpret):
    """Exhaustive fine stage over either packed model: the kernel of its
    dtype, the masks and the top-k (`bsr_predict_topk`)."""
    from repro.kernels.bsr_predict import ops as bsr_ops   # deferred: no cycle
    return bsr_ops.bsr_predict_topk(x, model, k, n_labels=n_labels,
                                    interpret=interpret)


def _top_blocks(coarse: Array, B: int, per_query: bool) -> Array:
    """Top-B row blocks from (n, R) coarse scores, sorted ascending: one
    (B,) list for the micro-batch from the max over its rows (static
    output shape), or with `per_query` an (n, B) list for each row. The
    sort makes B = R reproduce exhaustive scoring bit-for-bit (same float
    accumulation order into the same top-k input)."""
    if per_query:
        _, sel = jax.lax.top_k(coarse, B)
        return jnp.sort(sel, axis=1)
    _, sel = jax.lax.top_k(coarse.max(axis=0), B)
    return jnp.sort(sel)


def _pad_query(x: Array, Dp: int) -> Array:
    """x as float32, zero-padded to the coarse stage's width Dp."""
    xf = x.astype(jnp.float32)
    if xf.shape[1] < Dp:
        xf = jnp.pad(xf, ((0, 0), (0, Dp - xf.shape[1])))
    return xf


@functools.partial(jax.jit, static_argnames=("B", "per_query"))
def _shortlist_select(x: Array, centroids: Array, *, B: int,
                      per_query: bool) -> Array:
    """Matrix coarse stage: one (n, Dp) x (Dp, R) matmul, then the top-B
    row blocks (`_top_blocks`)."""
    coarse = _pad_query(x, centroids.shape[1]) @ centroids.T     # (n, R)
    return _top_blocks(coarse, B, per_query)


@functools.partial(jax.jit, static_argnames=("depth", "B", "per_query"))
def _tree_select(x: Array, nodes: Array, leaf_scores: Array, *, depth: int,
                 B: int, per_query: bool) -> Array:
    """Tree-routing coarse stage: descend the complete binary tree of
    hyperplanes (level-order `nodes`, one (Dp,) normal each) for `depth`
    static steps, read the reached leaf's (R,) per-row-block score row,
    then take the top-B row blocks as the matrix kinds do."""
    xf = _pad_query(x, nodes.shape[1])
    idx = jnp.zeros((xf.shape[0],), jnp.int32)
    for _ in range(depth):                         # static, tiny depth
        w = nodes[idx]                             # (n, Dp) routed normals
        go_right = (jnp.sum(xf * w, axis=1) >= 0.0).astype(jnp.int32)
        idx = 2 * idx + 1 + go_right
    leaf = idx - (2 ** depth - 1)
    return _top_blocks(leaf_scores[leaf], B, per_query)


@functools.partial(jax.jit, static_argnames=(
    "k", "n_labels", "max_per_row", "interpret"))
def _gather_topk(x, sel, model, *, k, n_labels, max_per_row, interpret):
    """Gathered fine stage over either packed model, the selection a
    runtime argument: (B,) shared by the micro-batch, or (n, B), one list
    per row, as `sel.ndim` says."""
    from repro.kernels.bsr_predict import ops as bsr_ops   # deferred: no cycle
    fn = (bsr_ops.bsr_predict_gather_pq_topk if sel.ndim == 2
          else bsr_ops.bsr_predict_gather_topk)
    return fn(x, model, sel, k, n_labels=n_labels, max_per_row=max_per_row,
              interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "k", "n_labels", "B", "per_query", "max_per_row", "interpret"))
def _shortlist_topk(x, centroids, model, *, k, n_labels, B, per_query,
                    max_per_row, interpret):
    """Matrix coarse stage and fine stage in one dispatch."""
    sel = _shortlist_select(x, centroids, B=B, per_query=per_query)
    return _gather_topk(x, sel, model, k=k, n_labels=n_labels,
                        max_per_row=max_per_row, interpret=interpret)


class DenseBackend:
    """Reference semantics: jitted dense scores + lax.top_k."""

    name = "dense"

    def __init__(self, W: Array, k: int, *, n_labels: int | None = None):
        self.k = k
        self.n_labels = int(n_labels if n_labels is not None else W.shape[0])
        self._W = jnp.asarray(W[:self.n_labels])   # drop any padding rows
        self._fn = functools.partial(_dense_topk, W=self._W, k=k)

    def warmup_key(self):
        return ("dense", self._W.shape, str(self._W.dtype), self.k)

    def topk(self, x: Array) -> tuple[Array, Array]:
        return self._fn(x)


class _PackedBackend:
    """Exhaustive scoring of a packed model through `_bsr_topk`; a
    subclass names its kind in `name`."""

    def __init__(self, model, k: int, *, n_labels: int | None = None,
                 interpret: bool | None = None):
        self.k = k
        self.n_labels = int(n_labels if n_labels is not None
                            else model.n_labels)
        self.model = model
        self._interpret = resolve_interpret(interpret)

    def warmup_key(self):
        # Leads with the kind tag AND the blocks' dtype: an int8 backend
        # over the same geometry as a fp32 bsr backend must never mark the
        # fp32 bucket warm (different executable, different numerics).
        m = self.model
        return (self.name, m.blocks.shape, str(jnp.asarray(m.blocks).dtype),
                m.shape, m.block_shape, m.orig_shape, self.k, self.n_labels,
                self._interpret)

    def topk(self, x: Array) -> tuple[Array, Array]:
        return _bsr_topk(x, self.model, k=self.k, n_labels=self.n_labels,
                         interpret=self._interpret)


class BsrBackend(_PackedBackend):
    """Packed block-sparse model through the Pallas predict kernel + top-k."""

    name = "bsr"

    def weight_passes(self, n: int) -> int:
        """Passes an n-row launch makes over the packed blocks, from the
        kernel's own layout choice."""
        m = self.model
        return bsr_kernel.weight_passes(n, *m.shape, m.block_shape[0])

    def topk_grouped(self, n: int) -> bool:
        """Whether an n-row launch's top-k takes the grouped form, from
        the rule on the score row's width (`ops.topk_grouped`)."""
        from repro.kernels.bsr_predict import ops as bsr_ops
        return bsr_ops.topk_grouped(self.model.shape[0], self.k)


class Int8Backend(_PackedBackend):
    """Exhaustive BSR scoring over the int8 per-block-scaled artifact.

    Accepts either the quantized artifact directly or a fp32
    `BlockSparseModel` (quantized here — identical bytes to the persisted
    checkpoint artifact, so legacy fp32-only checkpoints serve int8 too).
    The server counts no weight passes or grouped top-ks for it.
    """

    name = "int8"

    def __init__(self, model, k: int, *, n_labels: int | None = None,
                 interpret: bool | None = None):
        if isinstance(model, BlockSparseModel):
            model = quantize_block_sparse(model)
        super().__init__(model, k, n_labels=n_labels, interpret=interpret)


class ShortlistBackend:
    """Two-stage sub-linear scoring: coarse block shortlist + gathered fine
    stage over the packed BSR tiles of the selected row blocks only.

    The coarse stage is whatever the artifact holds (`artifact.kind`):
    "centroid" and "learned" are both one (n, Dp) x (Dp, R) matmul (block
    means vs a trained one-vs-rest meta-classifier — same serving math,
    different matrix), "tree" routes each query down a fixed-depth
    hyperplane tree to a leaf's per-block score row. Selection is shared
    per micro-batch by default; `per_query=True` gives each row its own
    top-B list, scored through the ragged-gather kernel.

    B (the shortlist width, in row blocks) is static per backend: one XLA
    compile per bucket, candidate fraction B / R. At B == R every
    per-query sorted top-B list provably equals the one shared sorted full
    list, so full width ALWAYS collapses to the shared kernel: the
    exhaustive bit-exactness contract rides on the proven path, and the
    ragged kernel serves only genuinely sub-linear B < R work. One caveat
    inherited from bucket padding: the shared coarse max runs over the
    padded micro-batch, and a padding row's coarse score is exactly 0 — on
    models whose true coarse scores are all negative, padding can steer
    (never widen) the selection. Per-query selection is immune: padding
    rows select for themselves and their results are dropped at un-pad.
    """

    name = "shortlist"

    def __init__(self, model: BlockSparseModel, artifact: ShortlistArtifact,
                 k: int, *, n_labels: int | None = None,
                 blocks: int | None = None, interpret: bool | None = None,
                 int8: bool = False, int8_model=None,
                 per_query: bool = False):
        from repro.kernels.bsr_predict import ops as bsr_ops
        artifact.validate_against(model)
        self.k = k
        self.n_labels = int(n_labels if n_labels is not None
                            else model.n_labels)
        self.model = model
        self.artifact = artifact
        self.kind = artifact.kind
        R = artifact.n_row_blocks
        self.B = min(int(blocks if blocks is not None
                         else artifact.default_blocks()), R)
        if self.B < 1:
            raise ValueError(f"shortlist width must be >= 1, got {self.B}")
        # Full-width collapse (see class docstring): B == R means every
        # query's sorted list is 0..R-1 — identical to the shared list.
        self.per_query = bool(per_query) and self.B < R
        self._centroids = jnp.asarray(artifact.centroids)
        self._tree = None
        if self.kind == "tree":
            self._tree = (jnp.asarray(artifact.tree_nodes),
                          jnp.asarray(artifact.tree_leaf_scores),
                          int(artifact.tree_depth))
        self._max_per_row = bsr_ops.max_blocks_per_row(model)
        self._interpret = resolve_interpret(interpret)
        # int8 composition: the coarse stage is unchanged (fp32 — tiny next
        # to the fine stage), the gathered fine stage scores quantized
        # tiles. Pass `int8_model` to reuse a persisted artifact; otherwise
        # quantize here (bit-identical either way).
        self.int8 = bool(int8)
        self.int8_model = None
        if self.int8:
            self.int8_model = (int8_model if int8_model is not None
                               else quantize_block_sparse(model))

    @property
    def candidate_fraction(self) -> float:
        """Fraction of row blocks the fine stage scores per query (shared
        selection charges the whole micro-batch the same B)."""
        return self.B / self.artifact.n_row_blocks

    def warmup_key(self):
        # `self.int8`, `self.kind` and `self.per_query` are part of the
        # key: int8 vs fp32 fine stages, tree vs matrix coarse stages, and
        # ragged vs shared gathers are different executables over the same
        # geometry and must not alias each other's warm buckets.
        m = self.model
        return ("shortlist", self.kind, self.per_query, self.int8,
                m.blocks.shape, str(jnp.asarray(m.blocks).dtype), m.shape,
                m.block_shape, m.orig_shape, self._centroids.shape, self.B,
                self._max_per_row, self.k, self.n_labels, self._interpret)

    def _select(self, x: Array) -> Array:
        """The selection the fine stage will score: (B,) shared, or (n, B)
        per-query, row-sorted either way."""
        if self.kind == "tree":
            nodes, leaf_scores, depth = self._tree
            return _tree_select(x, nodes, leaf_scores, depth=depth, B=self.B,
                                per_query=self.per_query)
        return _shortlist_select(x, self._centroids, B=self.B,
                                 per_query=self.per_query)

    def select_blocks(self, x: Array) -> np.ndarray:
        """Coarse-stage introspection: the sorted row-block ids the fine
        stage would score for this batch — (B,) shared or (n, B) per-query
        (benchmarks measure recall and candidate fraction through this)."""
        return np.asarray(self._select(jnp.asarray(x, jnp.float32)))

    def topk(self, x: Array) -> tuple[Array, Array]:
        fine = self.int8_model if self.int8 else self.model
        kw = dict(k=self.k, n_labels=self.n_labels,
                  max_per_row=self._max_per_row, interpret=self._interpret)
        if self.kind == "tree":
            return _gather_topk(x, self._select(x), fine, **kw)
        return _shortlist_topk(x, self._centroids, fine, B=self.B,
                               per_query=self.per_query, **kw)


class RelabelBackend:
    """Pack-time reorder unmapping: wraps any backend serving a checkpoint
    packed under a `label_order` permutation and maps its packed top-k ids
    back to original label ids (`order[packed_id]`), scores untouched.

    Sits at the backend layer (not the engine) so both the synchronous
    `step()` drain and the async server's direct `backend.topk` dispatch
    unmap identically; everything else — kernels, selection, warm-up —
    stays oblivious to the reorder. `__getattr__` delegates introspection
    (`select_blocks`, `model`, `candidate_fraction`, ...) to the inner
    backend."""

    def __init__(self, inner: PredictBackend, label_order):
        order = np.asarray(label_order, np.int64).reshape(-1)
        n = int(getattr(inner, "n_labels", order.shape[0]))
        if (order.shape[0] != n
                or not np.array_equal(np.sort(order), np.arange(n))):
            raise ValueError(
                f"label_order must be a permutation of range({n})")
        self.inner = inner
        self.name = inner.name
        self.k = inner.k
        self.n_labels = n
        self._order = jnp.asarray(order, jnp.int32)
        self._digest = hashlib.sha1(order.tobytes()).hexdigest()[:16]

    def warmup_key(self):
        key = getattr(self.inner, "warmup_key", lambda: None)()
        # The gather is one extra executable per shape; two engines over
        # the same inner geometry but different permutations must not mark
        # each other warm, hence the order digest.
        return None if key is None else ("relabel", self._digest, key)

    def topk(self, x: Array) -> tuple[Array, Array]:
        scores, labels = self.inner.topk(x)
        return scores, jnp.take(self._order, labels, axis=0)

    def __getattr__(self, name):
        return getattr(self.inner, name)


class ShardedBackend:
    """Mesh label-sharded local-topk + all-gather merge (paper §2.2.1)."""

    name = "sharded"

    def __init__(self, W: Array, k: int, mesh, *, label_axis: str = "model",
                 n_labels: int | None = None):
        self.k = k
        self.n_labels = int(n_labels if n_labels is not None else W.shape[0])
        n_shards = mesh.shape[label_axis]
        L = W.shape[0]
        Lp = ((L + n_shards - 1) // n_shards) * n_shards
        if Lp != L:                                 # shard-divisibility pad
            W = jnp.concatenate(
                [W, jnp.zeros((Lp - L, W.shape[1]), W.dtype)], axis=0)
        # Placed label-sharded once, so no request re-sends W to the mesh.
        # W is an argument of the jitted function, not a closure constant:
        # a captured array would be baked into every bucket's executable.
        self._W = jax.device_put(W, NamedSharding(mesh, P(label_axis, None)))
        self._fn = jax.jit(functools.partial(
            predict_topk_sharded, k=k, mesh=mesh, label_axis=label_axis,
            n_labels=self.n_labels))

    def warmup_key(self):
        return None        # mesh-bound executable: never share warm-up state

    def topk(self, x: Array) -> tuple[Array, Array]:
        return self._fn(x, self._W)


# ---------------------------------------------------------------------------
# Backend registry: kind -> factory(bsr, k, *, n_labels, mesh, label_axis,
# interpret) -> PredictBackend. New backends plug in via the decorator; the
# engine, the CLIs, and ServeSpec all resolve kinds through this one table.
# ---------------------------------------------------------------------------

_BACKEND_REGISTRY: dict[str, "object"] = {}


def register_backend(kind: str):
    """Decorator: plug a new predict backend into the serving registry.

    The factory receives the canonical model artifact and must return a
    `PredictBackend`::

        @register_backend("quantized")
        def _make_quantized(bsr, k, *, n_labels, mesh, label_axis,
                            interpret):
            return QuantizedBackend(bsr, k, n_labels=n_labels)

    After registration, `ServeSpec(backend="quantized")`,
    `XMCEngine.from_checkpoint(..., backend="quantized")` and the serving
    CLI all reach it — no engine code changes.
    """
    def deco(factory):
        if kind in _BACKEND_REGISTRY:
            raise ValueError(f"backend {kind!r} already registered")
        _BACKEND_REGISTRY[kind] = factory
        return factory
    return deco


def unregister_backend(kind: str) -> None:
    """Remove a registered backend kind (plugin teardown / tests)."""
    _BACKEND_REGISTRY.pop(kind, None)


def available_backends() -> tuple[str, ...]:
    """Every registered backend kind (built-ins + plugins), sorted."""
    return tuple(sorted(_BACKEND_REGISTRY))


@register_backend("dense")
def _make_dense_backend(bsr: BlockSparseModel, k: int, *, n_labels: int,
                        mesh, label_axis: str, interpret: bool):
    return DenseBackend(bsr.to_dense()[:n_labels, :bsr.n_features], k,
                        n_labels=n_labels)


@register_backend("bsr")
def _make_bsr_backend(bsr: BlockSparseModel, k: int, *, n_labels: int,
                      mesh, label_axis: str, interpret: bool,
                      int8=False, int8_model=None):
    if int8:      # ServeSpec(backend="bsr", int8=True) == the "int8" kind
        return Int8Backend(int8_model if int8_model is not None else bsr,
                           k, n_labels=n_labels, interpret=interpret)
    return BsrBackend(bsr, k, n_labels=n_labels, interpret=interpret)


@register_backend("sharded")
def _make_sharded_backend(bsr: BlockSparseModel, k: int, *, n_labels: int,
                          mesh, label_axis: str, interpret: bool):
    if mesh is None:
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(1, jax.device_count())
    return ShardedBackend(bsr.to_dense()[:n_labels, :bsr.n_features], k,
                          mesh, label_axis=label_axis, n_labels=n_labels)


@register_backend("int8")
def _make_int8_backend(bsr: BlockSparseModel, k: int, *, n_labels: int,
                       mesh, label_axis: str, interpret: bool,
                       int8_model=None):
    return Int8Backend(int8_model if int8_model is not None else bsr, k,
                       n_labels=n_labels, interpret=interpret)


@register_backend("shortlist")
def _make_shortlist_backend(bsr: BlockSparseModel, k: int, *, n_labels: int,
                            mesh, label_axis: str, interpret: bool,
                            shortlist=None, shortlist_blocks=None,
                            int8=False, int8_model=None,
                            shortlist_per_query=False):
    if shortlist is None:
        # Legacy checkpoint (or in-memory model) without the artifact:
        # exhaustive BSR scoring, same results, no sub-linear gate.
        if int8:
            return Int8Backend(int8_model if int8_model is not None else bsr,
                               k, n_labels=n_labels, interpret=interpret)
        return BsrBackend(bsr, k, n_labels=n_labels, interpret=interpret)
    return ShortlistBackend(bsr, shortlist, k, n_labels=n_labels,
                            blocks=shortlist_blocks, interpret=interpret,
                            int8=int8, int8_model=int8_model,
                            per_query=shortlist_per_query)


def make_backend(kind: str, bsr: BlockSparseModel, k: int, *,
                 n_labels: int | None = None, mesh=None,
                 label_axis: str = "model", interpret: bool | None = None,
                 shortlist: ShortlistArtifact | None = None,
                 shortlist_blocks: int | None = None,
                 int8: bool = False,
                 int8_model: Int8BlockSparseModel | None = None,
                 shortlist_per_query: bool = False,
                 label_order=None,
                 ) -> PredictBackend:
    """Build any registered backend from the one canonical model artifact
    (packed BSR) — a thin lookup over the registry.

    dense/sharded densify in memory, sliced back to the true (L, D) so
    block padding never surfaces; bsr serves the packed form directly (its
    kernel pads x internally and its top-k masks padding labels); shortlist
    adds the coarse candidate stage when a `ShortlistArtifact` is supplied.
    kind="int8" (or shortlist with int8=True) serves the quantized artifact
    — pass `int8_model` to reuse a checkpoint's persisted int8 arrays,
    else the fp32 blocks are quantized on the spot (identical bytes).
    `shortlist_per_query` flips the shortlist backend to per-query ragged
    selection. `label_order` (the pack-time reorder permutation recorded in
    the checkpoint manifest) wraps ANY backend in `RelabelBackend` so
    returned ids are original label ids.

    Factories registered before the shortlist kwargs existed keep working:
    keyword args are filtered down to what each factory's signature accepts
    (factories with **kwargs receive everything).
    """
    try:
        factory = _BACKEND_REGISTRY[kind]
    except KeyError:
        raise ValueError(f"unknown backend {kind!r}; expected one of "
                         f"{available_backends()}") from None
    n_labels = int(n_labels if n_labels is not None else bsr.n_labels)
    kwargs = dict(n_labels=n_labels, mesh=mesh, label_axis=label_axis,
                  interpret=interpret, shortlist=shortlist,
                  shortlist_blocks=shortlist_blocks, int8=int8,
                  int8_model=int8_model,
                  shortlist_per_query=shortlist_per_query)
    try:
        params = inspect.signature(factory).parameters
        if not any(p.kind is p.VAR_KEYWORD for p in params.values()):
            kwargs = {k2: v for k2, v in kwargs.items() if k2 in params}
    except (TypeError, ValueError):      # uninspectable callable: old contract
        kwargs = dict(n_labels=n_labels, mesh=mesh, label_axis=label_axis,
                      interpret=interpret)
    be = factory(bsr, k, **kwargs)
    if label_order is not None:
        be = RelabelBackend(be, label_order)
    return be


# ---------------------------------------------------------------------------
# Process-wide warm-up ledger. The jitted functions above make the sharing
# real (one XLA cache entry per computation); this ledger makes it visible
# and cheap: a (warmup_key, bucket, n_features) triple already warmed by ANY
# engine is skipped outright — the second engine's warmup() marks the bucket
# warm without a dispatch. Backends whose key is None (mesh-bound sharded,
# plugins without warmup_key) always dispatch.
# ---------------------------------------------------------------------------

_WARMUP_SEEN: set = set()
_WARMUP_STATS = {"dispatches": 0, "shared_hits": 0}


def reset_warmup_cache() -> None:
    """Forget all shared warm-up state (tests / benchmark isolation). Does
    not touch jax's own compile cache — only the skip-dispatch ledger."""
    _WARMUP_SEEN.clear()
    _WARMUP_STATS["dispatches"] = 0
    _WARMUP_STATS["shared_hits"] = 0


def warmup_cache_stats() -> dict[str, int]:
    """Counters since the last reset: `dispatches` (warm-up calls actually
    issued; each may still hit jax's compile cache) and `shared_hits`
    (bucket warm-ups skipped because an equal computation was already
    warmed by another engine this process)."""
    return dict(_WARMUP_STATS)


@dataclasses.dataclass
class XMCResult:
    """Answer to one request: top-k labels for each of its instances."""
    request_id: int
    scores: np.ndarray                 # (n_i, k)
    labels: np.ndarray                 # (n_i, k) true label ids


class XMCEngine:
    """Micro-batched top-k label serving over a `PredictBackend`.

    The engine owns the request queue, bucket padding, per-bucket warm-up
    compilation, and latency accounting; the backend owns the math.
    """

    def __init__(self, backend: PredictBackend,
                 buckets: Sequence[int] = DEFAULT_BUCKETS,
                 *, warmup: bool = True, n_features: int | None = None):
        self.backend = backend
        self.queue = MicroBatchQueue(buckets)
        self.stats = LatencyStats()
        self._warm: set[int] = set()
        self._n_features = n_features
        if warmup and n_features is not None:
            self.warmup()

    @property
    def n_features(self) -> int | None:
        """Feature dim the engine serves (from checkpoint meta or the first
        submitted request); None until either is known."""
        return self._n_features

    def adopt_n_features(self, n_features: int) -> None:
        """Pin the feature dim on an engine that does not know it yet (no
        checkpoint meta, no request seen). `XMCServer.swap` uses this so an
        in-memory replacement engine can be warmed for the server's buckets
        before the flip; adopting a CONFLICTING dim is refused like a
        mismatched request would be."""
        n_features = int(n_features)
        if self._n_features is not None and self._n_features != n_features:
            raise ValueError(f"engine already serves feature dim "
                             f"{self._n_features}, cannot adopt {n_features}")
        self._n_features = n_features

    # -- model loading ------------------------------------------------------

    @classmethod
    def from_checkpoint(cls, directory: str, *, backend: str = "bsr",
                        k: int = 5, mesh=None, interpret: bool | None = None,
                        buckets: Sequence[int] = DEFAULT_BUCKETS,
                        warmup: bool = True,
                        shortlist_blocks: int | None = None,
                        int8: bool = False,
                        shortlist_per_query: bool = False) -> "XMCEngine":
        """Serve the sparse artifact written by `BlockSparseModel.save`.

        Also picks up the shortlist artifact saved next to the BSR arrays
        when present — absent (legacy checkpoints), the "shortlist" backend
        silently degrades to exhaustive BSR scoring. backend="int8" (or
        `int8=True` composing with shortlist) serves the checkpoint's
        persisted int8 arrays, quantizing lazily when the checkpoint
        predates them. A checkpoint packed under a `label_order`
        permutation (ScheduleSpec.reorder_labels) is unmapped here: EVERY
        backend's returned ids are original label ids, exactly.
        """
        from repro.checkpoint.io import (load_block_sparse_int8,   # deferred:
                                         load_block_sparse_meta,   # no cycle
                                         load_shortlist)
        bsr, meta = BlockSparseModel.load(directory)
        n_labels = int(meta.get("n_labels", bsr.n_labels))
        int8_model = None
        if int8 or backend == "int8":
            int8_model, _ = load_block_sparse_int8(directory, model=bsr)
        be = make_backend(backend, bsr, k, n_labels=n_labels, mesh=mesh,
                          interpret=interpret,
                          shortlist=load_shortlist(directory),
                          shortlist_blocks=shortlist_blocks,
                          int8=int8, int8_model=int8_model,
                          shortlist_per_query=shortlist_per_query,
                          label_order=load_block_sparse_meta(
                              directory).get("label_order"))
        return cls(be, buckets, warmup=warmup,
                   n_features=int(meta.get("n_features", bsr.n_features)))

    @classmethod
    def from_dismec(cls, model, *, backend: str = "dense", k: int = 5,
                    mesh=None, block_shape: tuple[int, int] = (128, 128),
                    interpret: bool | None = None,
                    buckets: Sequence[int] = DEFAULT_BUCKETS,
                    warmup: bool = False,
                    shortlist_blocks: int | None = None,
                    int8: bool = False,
                    shortlist_per_query: bool = False) -> "XMCEngine":
        """Convenience: engine straight from an in-memory DiSMECModel (the
        shortlist artifact is built on the fly — no checkpoint needed)."""
        bsr = to_block_sparse(model.W, block_shape)
        be = make_backend(backend, bsr, k, n_labels=model.W.shape[0],
                          mesh=mesh, interpret=interpret,
                          shortlist=build_shortlist(bsr),
                          shortlist_blocks=shortlist_blocks, int8=int8,
                          shortlist_per_query=shortlist_per_query)
        return cls(be, buckets, warmup=warmup,
                   n_features=int(model.W.shape[1]))

    # -- serving ------------------------------------------------------------

    def ensure_warm(self, bucket: int) -> None:
        """Warm one bucket if this engine has not yet (step() and the async
        server share this so no request pays a compile mid-flight)."""
        if bucket not in self._warm:
            self.warmup([bucket])

    def warmup(self, buckets: Sequence[int] | None = None) -> int:
        """Compile the backend once per bucket shape (cold-start cost paid
        up front, not on the first unlucky request). Returns the number of
        buckets newly warmed for THIS engine; buckets another engine
        already warmed process-wide (same `warmup_key`) count but skip the
        dispatch entirely — see `warmup_cache_stats`."""
        assert self._n_features is not None, "n_features needed for warmup"
        key = getattr(self.backend, "warmup_key", lambda: None)()
        done = 0
        for b in (buckets or self.queue.buckets):
            if b in self._warm:
                continue
            gkey = None if key is None else (key, b, self._n_features)
            if gkey is not None and gkey in _WARMUP_SEEN:
                _WARMUP_STATS["shared_hits"] += 1
            else:
                x = jnp.zeros((b, self._n_features), jnp.float32)
                jax.block_until_ready(self.backend.topk(x))
                _WARMUP_STATS["dispatches"] += 1
                if gkey is not None:
                    _WARMUP_SEEN.add(gkey)
            self._warm.add(b)
            done += 1
        return done

    def submit(self, x: np.ndarray) -> int:
        """Enqueue one request of (n_i, D) instances; returns request id.

        Shape-checked at enqueue time: a mismatched request must never
        reach step(), where a mid-drain failure would lose the results of
        co-batched good requests.
        """
        if self._n_features is None:
            self._n_features = int(x.shape[1])
        elif x.shape[1] != self._n_features:
            raise ValueError(
                f"request feature dim {x.shape[1]} != engine feature dim "
                f"{self._n_features}")
        return self.queue.submit(np.asarray(x, np.float32))

    def step(self) -> list[XMCResult]:
        """Drain the queue: run every micro-batch, un-pad, return results.

        One `XMCResult` per request id, always — a request the queue split
        across micro-batches (oversize) has its rows re-coalesced in
        dispatch order before anything is returned. Latency is recorded per
        request from its own enqueue timestamp to the completion of its
        last micro-batch, so time spent waiting in the queue (between
        `submit` and this drain) is part of the number.
        """
        out: dict[int, list[tuple[np.ndarray, np.ndarray]]] = {}
        arrival_by_rid: dict[int, float] = {}
        done_by_rid: dict[int, float] = {}
        for mb in self.queue.drain():
            self.ensure_warm(mb.bucket)
            scores, labels = self.backend.topk(jnp.asarray(mb.x))
            jax.block_until_ready(labels)
            t_done = time.monotonic()
            # A split request completes with its LAST micro-batch: later
            # batches overwrite t_done, the arrival never changes.
            for rid, arrival in zip(mb.request_ids, mb.arrivals):
                arrival_by_rid[rid] = arrival
                done_by_rid[rid] = t_done
            scores, labels = np.asarray(scores), np.asarray(labels)
            for (rid, s), (_, l) in zip(mb.split(scores), mb.split(labels)):
                out.setdefault(rid, []).append((s, l))
        for rid in sorted(done_by_rid):
            self.stats.record_span(arrival_by_rid[rid], done_by_rid[rid])
        results = []
        for rid in sorted(out):
            parts = out[rid]
            results.append(XMCResult(
                request_id=rid,
                scores=np.concatenate([p[0] for p in parts], axis=0),
                labels=np.concatenate([p[1] for p in parts], axis=0)))
        return results

    def serve(self, requests: Iterable[np.ndarray]) -> list[XMCResult]:
        """Submit a whole request stream and drain it. Results are ordered
        by request id (== submission order)."""
        for x in requests:
            self.submit(x)
        return self.step()

    def server(self, **kwargs) -> "object":
        """Wrap this engine in the async continuous-batching loop
        (`serve.server.XMCServer`): `submit` returns futures, buckets
        launch on fill OR deadline, admission control sheds overload. The
        synchronous `step()` path stays available and bit-identical.
        Keyword args go to `XMCServer` (max_batch_delay_ms, max_queue,
        max_inflight, name, start)."""
        from repro.serve.server import XMCServer     # deferred: no cycle
        return XMCServer(self, **kwargs)

    def latency_summary(self) -> dict[str, float]:
        return self.stats.summary()
