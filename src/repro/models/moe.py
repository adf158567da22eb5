"""Mixture-of-Experts FFN: top-k routing, shared experts, expert parallelism.

Covers the two assigned MoE architectures:
  * qwen2-moe-a2.7b — 60 routed experts top-4 + 4 shared experts
    (per-expert d_ff 1408, shared 5632) [hf:Qwen/Qwen1.5-MoE-A2.7B]
  * mixtral-8x22b   — 8 routed experts top-2, SwiGLU d_ff 16384
    [arXiv:2401.04088]

Dispatch is sort-based (argsort by expert id + capacity clipping), not
one-hot-einsum: the GShard dispatch tensor is O(S^2 k) per group and blows
HBM at 4k x 256 shapes, while the sort path is O(n k) bookkeeping around
dense (E, C, d) batched matmuls — the TPU-friendly shape.

Distribution (DESIGN.md §5): this layer is an explicit shard_map island
inside the pjit graph. Tokens stay on their (pod, data) shard — dispatch is
LOCAL, so there is no token all-to-all at all; experts are *tensor*-parallel
(d_ff sharded over `model`, since neither 60 nor 8 divides a 16-way mesh)
with a single psum per layer. The router aux (load-balance) loss follows
Switch: E * sum_e f_e * p_e.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig

Array = jax.Array


def init_moe(cfg: ArchConfig, rng: Array, dtype) -> dict:
    d = cfg.d_model
    fe = cfg.moe_d_ff or cfg.d_ff
    E = cfg.n_experts
    k = jax.random.split(rng, 5)
    s = d ** -0.5
    p = {
        "router": (jax.random.normal(k[0], (d, E)) * s).astype(jnp.float32),
        "w1": (jax.random.normal(k[1], (E, d, fe)) * s).astype(dtype),
        "w3": (jax.random.normal(k[2], (E, d, fe)) * s).astype(dtype),
        "w2": (jax.random.normal(k[3], (E, fe, d)) * (fe ** -0.5)).astype(dtype),
    }
    if cfg.n_shared_experts:
        fs = cfg.shared_d_ff or (fe * cfg.n_shared_experts)
        kk = jax.random.split(k[4], 4)
        p["shared"] = {
            "w1": (jax.random.normal(kk[0], (d, fs)) * s).astype(dtype),
            "w3": (jax.random.normal(kk[1], (d, fs)) * s).astype(dtype),
            "w2": (jax.random.normal(kk[2], (fs, d)) * (fs ** -0.5)).astype(dtype),
            # qwen2-moe gates the shared expert output per token
            "gate": (jax.random.normal(kk[3], (d, 1)) * s).astype(dtype),
        }
    return p


def _dispatch_combine(xf: Array, probs: Array, top_k: int, capacity: int,
                      w1: Array, w3: Array, w2: Array,
                      model_axis: Optional[str]) -> Array:
    """Sort-based dispatch -> batched expert FFN -> weighted combine.

    xf (n, d) local tokens, probs (n, E) router probabilities.
    w1/w3 (E, d, f_shard), w2 (E, f_shard, d); psum over model_axis if given.
    """
    n, d = xf.shape
    E = probs.shape[1]
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)          # (n, k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)      # renormalize

    e_flat = gate_idx.reshape(-1)                              # (n*k,)
    w_flat = gate_vals.reshape(-1)
    tok_flat = jnp.arange(n * top_k, dtype=jnp.int32) // top_k

    order = jnp.argsort(e_flat)                                # stable
    e_s, tok_s, w_s = e_flat[order], tok_flat[order], w_flat[order]

    # Position of each routed token within its expert's capacity buffer.
    counts = jnp.zeros((E,), jnp.int32).at[e_s].add(1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(n * top_k, dtype=jnp.int32) - starts[e_s]
    keep = pos < capacity
    dst = jnp.where(keep, e_s * capacity + pos, E * capacity)  # overflow slot

    buf = jnp.zeros((E * capacity + 1, d), xf.dtype).at[dst].set(xf[tok_s])
    buf = buf[:-1].reshape(E, capacity, d)

    h = jnp.einsum("ecd,edf->ecf", buf, w1,
                   preferred_element_type=jnp.float32)
    g = jnp.einsum("ecd,edf->ecf", buf, w3,
                   preferred_element_type=jnp.float32)
    h = (jax.nn.silu(h) * g).astype(xf.dtype)
    y = jnp.einsum("ecf,efd->ecd", h, w2,
                   preferred_element_type=jnp.float32).astype(xf.dtype)

    # Combine BEFORE the TP psum: combine is linear in y, so
    # psum(combine(y)) == combine(psum(y)) — but the psum operand shrinks
    # from the padded capacity buffer (E, C, d) = k*capacity_factor x token
    # bytes to the token output (n, d). 2.5x less AR traffic for mixtral
    # (k=2, cf=1.25) — EXPERIMENTS.md SSPerf mixtral iteration m1.
    y_flat = jnp.concatenate(
        [y.reshape(E * capacity, d), jnp.zeros((1, d), y.dtype)])
    contrib = y_flat[jnp.where(keep, dst, E * capacity)] * w_s[:, None]
    out = jnp.zeros((n, d), xf.dtype).at[tok_s].add(contrib)
    if model_axis is not None:
        out = jax.lax.psum(out, model_axis)                    # f was sharded
    return out


def _shared_expert(p: dict, xf: Array,
                   model_axis: Optional[str] = None) -> Array:
    sh = p["shared"]
    h = jax.nn.silu(xf @ sh["w1"]) * (xf @ sh["w3"])   # fs possibly sharded
    y = h @ sh["w2"]
    if model_axis is not None:
        y = jax.lax.psum(y, model_axis)                # fs was sharded
    gate = jax.nn.sigmoid((xf @ sh["gate"]).astype(jnp.float32)).astype(y.dtype)
    return y * gate


def moe_ffn_local(cfg: ArchConfig, p: dict, xf: Array,
                  model_axis: Optional[str] = None,
                  w1=None, w3=None, w2=None) -> tuple[Array, Array]:
    """MoE FFN on local tokens xf (n, d). Returns (out, aux_loss)."""
    E, k = cfg.n_experts, cfg.moe_top_k
    n = xf.shape[0]
    capacity = max(int(n * k / E * cfg.capacity_factor), 4)
    logits = (xf @ p["router"].astype(xf.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    out = _dispatch_combine(xf, probs, k, capacity,
                            w1 if w1 is not None else p["w1"],
                            w3 if w3 is not None else p["w3"],
                            w2 if w2 is not None else p["w2"],
                            model_axis)
    if cfg.n_shared_experts:
        out = out + _shared_expert(p, xf, model_axis)
    # Switch-style load-balance loss: E * sum_e (token frac)_e * (prob mass)_e
    _, top1 = jax.lax.top_k(probs, 1)
    f_e = jnp.mean(jax.nn.one_hot(top1[:, 0], E, dtype=jnp.float32), axis=0)
    p_e = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(f_e * p_e)
    return out, aux


def moe_ffn(cfg: ArchConfig, p: dict, x: Array, *,
            mesh=None, batch_axes: tuple = (), model_axis: str = "model",
            ) -> tuple[Array, Array]:
    """MoE FFN on (B, T, d). With a mesh: shard_map island — tokens stay on
    their (pod, data) shard (local dispatch, no all-to-all), expert d_ff
    sharded over `model` with one psum."""
    B, T, d = x.shape

    if mesh is None:
        out, aux = moe_ffn_local(cfg, p, x.reshape(B * T, d))
        return out.reshape(B, T, d), aux

    from jax.sharding import PartitionSpec as P
    n_batch_shards = 1
    for a in batch_axes:
        n_batch_shards *= mesh.shape[a]
    if batch_axes and B % n_batch_shards == 0:
        bspec = P(batch_axes, None, None)
    elif "data" in mesh.shape and B % mesh.shape["data"] == 0:
        bspec = P("data", None, None)
    else:
        bspec = P(None, None, None)     # B=1 decode: tokens replicated
    fsdp = "data"

    def body(xl, router, w1, w3, w2, shared_p):
        # FSDP: expert weights arrive d-sharded over `data`; gather per layer
        # (the usual ZeRO-3 all-gather, explicit here).
        w1 = jax.lax.all_gather(w1, fsdp, axis=1, tiled=True)   # (E, d, f/TP)
        w3 = jax.lax.all_gather(w3, fsdp, axis=1, tiled=True)
        w2 = jax.lax.all_gather(w2, fsdp, axis=2, tiled=True)   # (E, f/TP, d)
        router = jax.lax.all_gather(router, fsdp, axis=0, tiled=True)
        pl = {"router": router, "w1": w1, "w3": w3, "w2": w2}
        if shared_p is not None:
            sh = dict(shared_p)
            sh["w1"] = jax.lax.all_gather(sh["w1"], fsdp, axis=0, tiled=True)
            sh["w3"] = jax.lax.all_gather(sh["w3"], fsdp, axis=0, tiled=True)
            sh["w2"] = jax.lax.all_gather(sh["w2"], fsdp, axis=1, tiled=True)
            sh["gate"] = jax.lax.all_gather(sh["gate"], fsdp, axis=0,
                                            tiled=True)
            pl["shared"] = sh
        Bl, Tl, _ = xl.shape
        out, aux = moe_ffn_local(cfg, pl, xl.reshape(Bl * Tl, d),
                                 model_axis=model_axis)
        aux = jax.lax.pmean(aux, batch_axes) if batch_axes else aux
        return out.reshape(Bl, Tl, d), aux

    shared = p.get("shared")
    shared_specs = None
    if shared is not None:
        shared_specs = {"w1": P(fsdp, model_axis), "w3": P(fsdp, model_axis),
                        "w2": P(model_axis, fsdp), "gate": P(fsdp, None)}
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(bspec, P(fsdp, None), P(None, fsdp, model_axis),
                  P(None, fsdp, model_axis), P(None, model_axis, fsdp),
                  shared_specs),
        out_specs=(bspec, P()), check_vma=False)
    return fn(x, p["router"], p["w1"], p["w3"], p["w2"], shared)
