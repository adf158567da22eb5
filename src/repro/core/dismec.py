"""DiSMEC training: double layer of parallelization, in JAX.

Paper Algorithm 1, re-mapped to a TPU mesh (DESIGN.md §2):

  layer 1 — label batches over nodes  ->  label axis sharded over the mesh
            `model` axis with shard_map; each device owns an L/n_model shard.
            For label sets larger than fits in memory at once, an outer
            *sequential* loop over label batches (paper's `for b in 0..B`)
            wraps the sharded solve, exactly like the paper's node dispatch.
  layer 2 — one label per OpenMP core ->  the per-device shard is solved by
            ONE batched TRON loop (core/tron.py) driving the MXU.

X is never replicated per label (paper §2.1): every binary problem shares the
same device buffer. Beyond the paper, `shard_data=True` additionally shards
the *instance* axis over the mesh `data` axis and reconstitutes gradients /
Hessian-vector products with `psum` — the collective-based Newton-CG the
paper could not express on a CPU cluster.

Layer 1's sequential batch loop itself lives in train/xmc.py
(`XMCTrainJob`) under the declarative session API (repro.xmc_api.fit):
`train` and `train_sharded` here are thin adapters over that one spec
path, and this module contributes the layer-2 engine (`make_batch_solver`,
warm-startable via a per-batch W0) every path shares. The obj-grad/Hv
implementations live in a solver-ops registry (`register_solver_ops`):
"jnp" and "pallas" are built in, and `SolverSpec(ops=...)` /
`DiSMECConfig(ops=...)` select plugins without touching the optimizer.

All three injection sites — the jnp losses path, the Pallas-kernel path
(`use_pallas=True`, interpret/compiled auto-selected per backend via
`cfg.pallas_interpret=None`), and the data-sharded psum closures — speak
core/tron.py's margin-caching protocol: `obj_grad(W) -> (f, grad, act)`
derives the active mask from the one score pass it already ran, and
`hvp(V, act)` consumes that cached mask, so no CG iteration ever re-runs
the (L, D) x (D, N) score matmul just to rebuild the active set.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import losses
from repro.core.tron import TronResult, tron_solve
from repro.core.pruning import prune

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class DiSMECConfig:
    """Hyper-parameters of Algorithm 1."""
    C: float = 1.0               # error/regularization trade-off (Eq. 2.2)
    delta: float = 0.01          # ambiguity threshold Delta (paper fixes 0.01)
    eps: float = 0.01            # TRON relative gradient tolerance
    max_newton: int = 50
    max_cg: int = 40
    label_batch: int = 1000      # paper's per-node batch size (layer 1)
    use_pallas: bool = False     # route obj/grad + Hv through Pallas kernels
    # Pallas execution mode: None auto-selects per backend (compiled Mosaic
    # on TPU, interpreter elsewhere — compat.default_pallas_interpret);
    # True/False force it. Only consulted when use_pallas=True.
    pallas_interpret: Optional[bool] = None
    # Solver-ops registry kind (see `register_solver_ops`). None derives the
    # kind from `use_pallas` ("pallas"/"jnp"); a registered plugin name
    # routes obj/grad + Hv through that factory instead.
    ops: Optional[str] = None

    def ops_kind(self) -> str:
        return self.ops or ("pallas" if self.use_pallas else "jnp")


# ---------------------------------------------------------------------------
# Solver-ops registry: how obj/grad + Hv are computed for a label batch.
# ---------------------------------------------------------------------------

# kind -> factory(X, S, cfg) -> (obj_grad, hvp) speaking the margin-caching
# protocol: obj_grad(W) -> (f, grad, act_aux), hvp(V, act_aux) -> H V.
SOLVER_OPS: dict[str, Callable] = {}


def register_solver_ops(kind: str):
    """Decorator: plug a new obj-grad/Hv implementation into the solver.

    The factory receives (X (N, D), S (L, N), cfg: DiSMECConfig) and must
    return the margin-caching protocol pair (see core/tron.py). Select it
    with `DiSMECConfig(ops=kind)` / `SolverSpec(ops=kind)` — no engine or
    scheduler code needs touching.
    """
    def deco(factory):
        if kind in SOLVER_OPS:
            raise ValueError(f"solver ops {kind!r} already registered")
        SOLVER_OPS[kind] = factory
        return factory
    return deco


def unregister_solver_ops(kind: str) -> None:
    """Remove a registered solver-ops kind (plugin teardown / tests)."""
    SOLVER_OPS.pop(kind, None)


def available_solver_ops() -> tuple[str, ...]:
    return tuple(sorted(SOLVER_OPS))


@register_solver_ops("jnp")
def _jnp_solver_ops(X: Array, S: Array, cfg: "DiSMECConfig"):
    obj_grad = lambda W: losses.objective_grad_act(W, X, S, cfg.C)
    hvp = lambda V, act: losses.hessian_vp(V, X, act, cfg.C)
    return obj_grad, hvp


@register_solver_ops("pallas")
def _pallas_solver_ops(X: Array, S: Array, cfg: "DiSMECConfig"):
    from repro.kernels.hinge import ops as hinge_ops
    from repro.kernels.hvp import ops as hvp_ops
    interp = cfg.pallas_interpret
    obj_grad = lambda W: hinge_ops.objective_grad_act(
        W, X, S, cfg.C, interpret=interp)
    hvp = lambda V, act: hvp_ops.hessian_vp(V, X, act, cfg.C,
                                            interpret=interp)
    return obj_grad, hvp


@dataclasses.dataclass
class DiSMECModel:
    """Learnt matrix W_{L,D} (paper notation transposed: we store (L, D)).

    Stored pruned: exact zeros where |w| < delta. `blocks` mirrors the paper's
    per-batch block matrices W^1..W^B used for distributed prediction.
    """
    W: Array                    # (L, D), pruned
    delta: float
    n_labels: int               # true L before padding

    @property
    def nnz(self) -> int:
        return int(jnp.sum(self.W != 0.0))

    def size_bytes(self, bytes_per_weight: int = 8) -> int:
        """Sparse storage cost: (value, index) pairs, as the paper counts."""
        return self.nnz * bytes_per_weight

    def dense_size_bytes(self, bytes_per_weight: int = 4) -> int:
        return self.W.shape[0] * self.W.shape[1] * bytes_per_weight


def signs_from_labels(Y: Array) -> Array:
    """Y (N, L) in {0,1}  ->  S (L, N) in {+1,-1} (paper's s_l vectors)."""
    return (2.0 * Y.T - 1.0).astype(jnp.float32)


def _make_fns(X: Array, S: Array, cfg: "DiSMECConfig"):
    """The margin-caching TRON protocol pair (core/tron.py): obj_grad(W) ->
    (f, grad, act) and hvp(V, act), built by the registered solver-ops
    factory `cfg.ops_kind()` names. The active mask is produced by the same
    score pass that computes f/grad — on the Pallas path it streams out of
    the fused hinge kernel tile-by-tile, so no separate mask matmul exists
    anywhere."""
    kind = cfg.ops_kind()
    try:
        factory = SOLVER_OPS[kind]
    except KeyError:
        raise ValueError(f"unknown solver ops {kind!r}; registered kinds: "
                         f"{available_solver_ops()}") from None
    return factory(X, S, cfg)


# ---------------------------------------------------------------------------
# Single-host solve (used per label batch, and as the shard body).
# ---------------------------------------------------------------------------

def train_label_batch(X: Array, S: Array, cfg: DiSMECConfig,
                      W0: Optional[Array] = None) -> TronResult:
    """Solve all labels in S at once (layer-2 parallelism).

    A non-None W0 is treated as a warm start: the relative stopping rule
    is anchored at the cold-start gradient ||g(0)|| (one extra obj/grad
    evaluation), not at the warm iterate's already-small ||g(W0)|| —
    otherwise the tolerance would tighten and drive converged labels
    through pointless extra Newton steps.
    """
    L, _ = S.shape
    D = X.shape[1]
    obj_grad, hvp = _make_fns(X, S, cfg)
    gnorm_ref = None
    if W0 is None:
        W0 = jnp.zeros((L, D), jnp.float32)
    else:
        _, g_zero, _ = obj_grad(jnp.zeros_like(W0))
        gnorm_ref = jnp.linalg.norm(g_zero, axis=-1)
    return tron_solve(obj_grad, hvp, W0, eps=cfg.eps,
                      max_newton=cfg.max_newton, max_cg=cfg.max_cg,
                      gnorm_ref=gnorm_ref)


def train(X: Array, Y: Array, cfg: DiSMECConfig = DiSMECConfig()) -> DiSMECModel:
    """Algorithm 1 on one device: sequential label batches (layer 1),
    batched TRON per batch (layer 2), Delta-pruning per batch (step 7).

    Thin adapter over the one spec-driven session path (repro.xmc_api):
    the config becomes an `XMCSpec` and runs through the same scheduler
    `fit()` drives, with the in-memory assembly step 11. Use
    `repro.xmc_api.fit(X, Y, spec, out_dir)` instead to stream the batches
    straight to a servable sparse checkpoint and never assemble W at all.
    """
    from repro.xmc_api import spec_from_config, job_from_spec   # no cycle
    return job_from_spec(spec_from_config(cfg)).run(X, Y).model


# ---------------------------------------------------------------------------
# Mesh-sharded solve: labels over `model`, optionally instances over `data`.
# ---------------------------------------------------------------------------

def balance_permutation(Y: Array, n_shards: int) -> np.ndarray:
    """Frequency-balanced label->shard assignment (beyond paper, DESIGN §2).

    The batched TRON loop runs until the SLOWEST label of a shard converges;
    head labels (many positives, many active-set flips) take more Newton
    steps than tail labels (1-3). Sorting labels by frequency and dealing
    them round-robin gives every shard the same head/tail mix, so shard
    wall-times equalize. Returns a permutation `perm` such that label
    perm[i] goes to slot i (shards are contiguous slot blocks)."""
    counts = np.asarray(Y).sum(axis=0)
    order = np.argsort(-counts, kind="stable")       # head labels first
    L = len(order)
    per = (L + n_shards - 1) // n_shards
    # Greedy capacity-constrained balancing (LPT scheduling): biggest label
    # first, always into the lightest shard with room. Round-robin dealing
    # is not enough under Eq. 1.1 — the rank-1 label alone outweighs whole
    # shards (measured 4.9x vs 53x naive; greedy gets <2x).
    mass = np.zeros(n_shards)
    members: list[list[int]] = [[] for _ in range(n_shards)]
    for lab in order:
        open_shards = [s for s in range(n_shards) if len(members[s]) < per]
        s = min(open_shards, key=lambda i: (mass[i], i))
        members[s].append(int(lab))
        mass[s] += counts[lab]
    perm = np.asarray([lab for m in members for lab in m], dtype=np.int64)
    return perm


class BatchSolve(NamedTuple):
    """One label batch's solve: the Delta-pruned weights and, per label,
    the Newton and CG iterations TRON spent on it."""
    W: Array            # (rows, D), pruned
    n_newton: Array     # (rows,) int32
    n_cg: Array         # (rows,) int32


def make_batch_solver(X: Array, cfg: DiSMECConfig, mesh: Optional[Mesh] = None,
                      *, label_axis: str = "model", data_axis: str = "data",
                      shard_data: bool = False, warm: bool = False):
    """Layer 2 of Algorithm 1 as a reusable jitted solver: (S (rows, N),
    W0 (rows, D) or None) -> `BatchSolve` (Delta-pruned W (rows, D) plus
    per-label iteration counts), rows a multiple of the label-shard count
    when a mesh is given. The one code path behind
    `train`, `train_sharded` and the streaming scheduler (train/xmc.py) —
    the scheduler keeps every label batch the same padded shape so all
    batches share one executable.

    mesh=None        : single-device batched TRON.
    shard_data=False : paper-faithful — X replicated per label-shard "node".
    shard_data=True  : beyond-paper — X sharded over `data`, grad/Hv psum'd.
                       N not divisible by the data axis is handled by padding
                       X with zero rows and S with all-negative sign columns:
                       a zero instance contributes nothing to the gradient or
                       the Hessian-vector product (every term carries a factor
                       of x = 0), and its constant C contribution to the
                       squared-hinge objective (z = 1 - s*0 = 1, active) is
                       subtracted back out after the psum, so the padded
                       objective is exactly the unpadded one.
    warm=True        : the returned solver expects warm-start W0s (a prior
                       checkpoint's rows) and anchors TRON's relative
                       stopping rule at ||g(0)|| — the cold-start tolerance
                       — via one extra obj/grad evaluation at W=0 per batch.
                       Without the anchor a warm W0's small gradient would
                       TIGHTEN the tolerance and un-converge every label.

    With a mesh, X is placed on it once, here, so no batch re-sends it to
    the devices. A host (numpy) X goes to each device as its own slice; a
    device-resident X would first be cut into every device's slice on its
    own device, which needs that device to hold X several times over.
    """
    xp = jnp if isinstance(X, jax.Array) else np
    X = xp.asarray(X, xp.float32)
    D = X.shape[1]

    def run_tron(obj_grad, hvp, W0: Array) -> BatchSolve:
        ref = None
        if warm:
            _, g_zero, _ = obj_grad(jnp.zeros_like(W0))
            ref = jnp.linalg.norm(g_zero, axis=-1)
        res = tron_solve(obj_grad, hvp, W0, eps=cfg.eps,
                         max_newton=cfg.max_newton, max_cg=cfg.max_cg,
                         gnorm_ref=ref)
        # Step 7 (prune) runs on device.
        return BatchSolve(prune(res.W, cfg.delta), res.n_newton, res.n_cg)

    def solve_local(X_in: Array, S_in: Array, W0: Array) -> BatchSolve:
        obj_grad, hvp = _make_fns(X_in, S_in, cfg)
        return run_tron(obj_grad, hvp, W0)

    if mesh is None:
        X = jnp.asarray(X)
        # X stays a traced argument (not a captured constant): XLA would
        # otherwise try to constant-fold whole X contractions at compile.
        jitted = jax.jit(solve_local)

        def solve_single(S: Array, W0: Optional[Array] = None) -> BatchSolve:
            if W0 is None:
                W0 = jnp.zeros((S.shape[0], D), jnp.float32)
            return jitted(X, S, W0)
        return solve_single

    n_pad = 0
    if not shard_data:
        s_spec = P(label_axis, None)
        x_spec = P()                                    # replicated
    else:
        n_data = mesh.shape[data_axis]
        N = X.shape[0]
        n_pad = (-N) % n_data                           # instance padding
        if n_pad:
            X = xp.concatenate([X, xp.zeros((n_pad, D), X.dtype)], axis=0)
        s_spec = P(label_axis, data_axis)
        x_spec = P(data_axis, None)
    X = jax.device_put(X, NamedSharding(mesh, x_spec))

    def solve_shard(X_sh: Array, S_sh: Array, W0_sh: Array) -> BatchSolve:
        if shard_data:
            # Margin-caching protocol over the data axis: the act payload is
            # the LOCAL (rows, N/n_data) mask of this shard's instance slice
            # — the Hv psum reconstitutes the global product from the cached
            # local masks, so CG does one local score pass per iteration.
            def obj_grad(W):
                scores = W @ X_sh.T
                z = 1.0 - S_sh * scores
                act = (z > 0.0).astype(scores.dtype)
                r = act * (scores - S_sh)
                f_loc = cfg.C * jnp.sum(act * z * z, axis=-1)
                g_loc = 2.0 * cfg.C * (r @ X_sh)
                f = (jnp.sum(W * W, axis=-1)
                     + jax.lax.psum(f_loc, data_axis) - cfg.C * n_pad)
                g = 2.0 * W + jax.lax.psum(g_loc, data_axis)
                return f, g, act

            def hvp(V, act):
                Xv = V @ X_sh.T
                loc = 2.0 * cfg.C * ((act * Xv) @ X_sh)
                return 2.0 * V + jax.lax.psum(loc, data_axis)

            return run_tron(obj_grad, hvp, W0_sh)
        return solve_local(X_sh, S_sh, W0_sh)

    shmapped = jax.shard_map(
        solve_shard, mesh=mesh,
        in_specs=(x_spec, s_spec, P(label_axis, None)),
        out_specs=BatchSolve(P(label_axis, None), P(label_axis),
                             P(label_axis)),
        check_vma=False)

    def solve(X_in: Array, S: Array, W0: Array) -> BatchSolve:
        if n_pad:
            S = jnp.concatenate(
                [S, -jnp.ones((S.shape[0], n_pad), S.dtype)], axis=1)
        return shmapped(X_in, S, W0)

    jitted = jax.jit(solve)

    def solve_meshed(S: Array, W0: Optional[Array] = None) -> BatchSolve:
        if W0 is None:
            W0 = jnp.zeros((S.shape[0], D), jnp.float32)
        return jitted(X, S, W0)
    return solve_meshed


def train_sharded(X: Array, Y: Array, cfg: DiSMECConfig, mesh: Mesh,
                  *, label_axis: str = "model", data_axis: str = "data",
                  shard_data: bool = False,
                  balance: bool = False) -> DiSMECModel:
    """Double parallelization on a mesh (paper layer 1 == label sharding).

    Thin wrapper over the batch-scheduler code path (train/xmc.py): the
    outer label-batch loop (cfg.label_batch) wraps the mesh-sharded solve,
    exactly like the paper's node dispatch — the old one-shot behaviour is
    cfg.label_batch >= n_labels.

    shard_data=False : paper-faithful — X replicated per label-shard "node".
    shard_data=True  : beyond-paper — X sharded over `data`, grad/Hv psum'd
                       (non-divisible N handled by zero-instance padding,
                       see `make_batch_solver`).
    balance=True     : beyond-paper — frequency-balanced label shards
                       (equalizes per-shard TRON wall time; solution is
                       identical, labels are permuted and un-permuted).
    """
    from repro.xmc_api import spec_from_config, job_from_spec   # no cycle
    spec = spec_from_config(cfg, label_axis=label_axis, data_axis=data_axis,
                            shard_data=shard_data, balance=balance)
    return job_from_spec(spec, mesh=mesh).run(X, Y).model
