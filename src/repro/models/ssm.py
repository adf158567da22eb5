"""Recurrent sequence-mixing layers: xLSTM (mLSTM + sLSTM) and Mamba-style SSD.

Covers the SSM/hybrid assigned architectures:
  * xlstm-125m  — sLSTM + mLSTM blocks [arXiv:2405.04517]
  * hymba-1.5b  — parallel attention + Mamba heads  [arXiv:2411.13676]

All three mixers expose the same two entry points:
  <mixer>(cfg, p, x)                       full-sequence (train / prefill)
  <mixer>_decode(cfg, p, x, state)         one token, O(1) state update

mLSTM trains in a CHUNKWISE-parallel form (chunk 256): intra-chunk quadratic
attention-like term + inter-chunk recurrent state carried by lax.scan — the
standard gated-linear-attention decomposition, adapted for TPU so the (T, T)
decay matrix never materializes beyond a chunk. Gate stabilization follows
the xLSTM paper's max-state m_t trick, done per chunk boundary.

sLSTM is inherently sequential (hidden-state mixing) and runs as lax.scan
over time with per-head block-diagonal recurrence.

Decode states are pytrees of fixed-shape arrays — they live in the serving
cache next to the attention KV blocks (models/kvcache.py). long_500k decode
is O(1) for all of these — the reason the SSM/hybrid archs run that shape
natively (DESIGN.md §Arch-applicability).
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig

Array = jax.Array
CHUNK = 256


# ===========================================================================
# mLSTM (matrix memory, exponential gating) — xLSTM's parallel workhorse
# ===========================================================================

def init_mlstm(cfg: ArchConfig, rng: Array, dtype) -> dict:
    d = cfg.d_model
    H = cfg.mlstm_heads or cfg.n_heads
    hd = d // H
    k = jax.random.split(rng, 6)
    s = d ** -0.5
    return {
        "wq": (jax.random.normal(k[0], (d, d)) * s).astype(dtype),
        "wk": (jax.random.normal(k[1], (d, d)) * s).astype(dtype),
        "wv": (jax.random.normal(k[2], (d, d)) * s).astype(dtype),
        "wo": (jax.random.normal(k[3], (d, d)) * s).astype(dtype),
        "w_if": (jax.random.normal(k[4], (d, 2 * H)) * s).astype(dtype),
        "b_if": jnp.concatenate([jnp.zeros((H,)), 3.0 * jnp.ones((H,))]
                                ).astype(jnp.float32),
        "ln": jnp.ones((d,), jnp.float32),      # per-head group-norm scale
    }


class MLSTMState(NamedTuple):
    C: Array    # (B, H, hd, hd) matrix memory
    n: Array    # (B, H, hd)     normalizer
    m: Array    # (B, H)         max-gate stabilizer (log space)


def mlstm_init_state(cfg: ArchConfig, B: int, dtype=jnp.float32) -> MLSTMState:
    H = cfg.mlstm_heads or cfg.n_heads
    hd = cfg.d_model // H
    return MLSTMState(C=jnp.zeros((B, H, hd, hd), dtype),
                      n=jnp.zeros((B, H, hd), dtype),
                      m=jnp.full((B, H), -1e30, dtype))


def _mlstm_gates(p: dict, x: Array, H: int):
    """Log input/forget gates, (B, T, H) each, f via log-sigmoid."""
    g = (x @ p["w_if"]).astype(jnp.float32) + p["b_if"]
    log_i = g[..., :H]                       # i_t = exp(itilde): log_i = itilde
    log_f = jax.nn.log_sigmoid(g[..., H:])   # f_t = sigmoid(ftilde)
    return log_i, log_f


def _heads(x: Array, H: int) -> Array:
    B, T, d = x.shape
    return x.reshape(B, T, H, d // H).transpose(0, 2, 1, 3)  # (B, H, T, hd)


def mlstm(cfg: ArchConfig, p: dict, x: Array, return_state: bool = False):
    """Chunkwise-parallel mLSTM over the full sequence. x: (B, T, d)."""
    B, T, d = x.shape
    H = cfg.mlstm_heads or cfg.n_heads
    hd = d // H
    nc = (T + CHUNK - 1) // CHUNK
    Tp = nc * CHUNK
    if Tp != T:
        x = jnp.pad(x, ((0, 0), (0, Tp - T), (0, 0)))

    q = _heads(x @ p["wq"], H) / math.sqrt(hd)   # (B, H, Tp, hd)
    k = _heads(x @ p["wk"], H)
    v = _heads(x @ p["wv"], H)
    log_i, log_f = _mlstm_gates(p, x, H)          # (B, Tp, H)
    log_i = log_i.transpose(0, 2, 1)              # (B, H, Tp)
    log_f = log_f.transpose(0, 2, 1)

    # Reshape into chunks: (nc, B, H, CHUNK, ...)
    def chunked(a):
        tail = a.shape[3:]                        # () or (hd,)
        return jnp.moveaxis(a.reshape(B, H, nc, CHUNK, *tail), 2, 0)

    qc = chunked(q)                               # (nc, B, H, CHUNK, hd)
    kc = chunked(k)
    vc = chunked(v)
    lic = chunked(log_i)                          # (nc, B, H, CHUNK)
    lfc = chunked(log_f)

    state0 = mlstm_init_state(cfg, B)

    def scan_chunk(state, inp):
        """Exactly matches the per-token decode recurrence.

        Let F_t = sum_{u<=t} lf_u within the chunk. The decode stabilizer
        satisfies m_t = F_t + M_t with M_t = max(m_in, cummax_{s<=t}(li_s - F_s));
        stored states carry units exp(m). In units exp(m_t):
          intra weight (source s <= t): exp(li_s - F_s - M_t)
          carried-state weight:         exp(m_in - M_t)
        """
        qx, kx, vx, li, lf = inp                  # (B, H, CHUNK, ...) leading
        C_in, n_in, m_in = state.C, state.n, state.m
        F = jnp.cumsum(lf, axis=-1)               # (B, H, W)
        a = li - F                                # (B, H, W) source log-weight
        M = jnp.maximum(m_in[..., None], jax.lax.cummax(a, axis=a.ndim - 1))

        # Intra-chunk term: w[t, s] = exp(a_s - M_t), s <= t.
        wmat = jnp.exp(a[..., None, :] - M[..., :, None])
        causal = jnp.tril(jnp.ones((CHUNK, CHUNK), bool))
        scores = jnp.einsum("bhtd,bhsd->bhts", qx, kx,
                            preferred_element_type=jnp.float32)
        w = jnp.where(causal, wmat * scores, 0.0)
        h_intra = jnp.einsum("bhts,bhsd->bhtd", w, vx.astype(jnp.float32))
        den_intra = jnp.sum(w, axis=-1)

        # Carried state term.
        carry_w = jnp.exp(m_in[..., None] - M)    # (B, H, W)
        h_inter = jnp.einsum("bhtd,bhde->bhte", qx.astype(jnp.float32),
                             C_in) * carry_w[..., None]
        den_inter = jnp.einsum("bhtd,bhd->bht", qx.astype(jnp.float32),
                               n_in) * carry_w

        num = h_intra + h_inter
        den = den_intra + den_inter
        h = num / jnp.maximum(jnp.abs(den), 1.0)[..., None]

        # End-of-chunk state, in units exp(m_out), m_out = F_W + M_W.
        M_W = M[..., -1]
        w_s = jnp.exp(a - M_W[..., None])         # (B, H, W)
        keep = jnp.exp(m_in - M_W)
        C_out = keep[..., None, None] * C_in + \
            jnp.einsum("bhs,bhsd,bhse->bhde", w_s, kx.astype(jnp.float32),
                       vx.astype(jnp.float32))
        n_out = keep[..., None] * n_in + \
            jnp.einsum("bhs,bhsd->bhd", w_s, kx.astype(jnp.float32))
        m_out = F[..., -1] + M_W
        return MLSTMState(C=C_out, n=n_out, m=m_out), h

    final, hs = jax.lax.scan(scan_chunk, state0, (qc, kc, vc, lic, lfc))
    h = hs.transpose(1, 2, 0, 3, 4).reshape(B, H, Tp, hd)   # (B,H,T,hd)
    h = h.transpose(0, 2, 1, 3).reshape(B, Tp, d)[:, :T]
    h = _group_rmsnorm(h, p["ln"], H)
    out = (h.astype(x.dtype) @ p["wo"]).astype(x.dtype)  # bf16 pre-AR (SSPerf)
    if return_state:
        return out, final
    return out


def _group_rmsnorm(x: Array, scale: Array, H: int, eps: float = 1e-6) -> Array:
    """Per-head RMS norm on flattened (B, T, d=H*hd)."""
    B, T, d = x.shape
    xs = x.reshape(B, T, H, d // H).astype(jnp.float32)
    xs = xs * jax.lax.rsqrt(jnp.mean(xs * xs, axis=-1, keepdims=True) + eps)
    return (xs.reshape(B, T, d) * scale).astype(x.dtype)


def mlstm_decode(cfg: ArchConfig, p: dict, x: Array,
                 state: MLSTMState) -> tuple[Array, MLSTMState]:
    """One-token recurrent step. x: (B, 1, d)."""
    B, _, d = x.shape
    H = cfg.mlstm_heads or cfg.n_heads
    hd = d // H
    q = (x @ p["wq"]).reshape(B, H, hd) / math.sqrt(hd)
    k = (x @ p["wk"]).reshape(B, H, hd)
    v = (x @ p["wv"]).reshape(B, H, hd)
    log_i, log_f = _mlstm_gates(p, x, H)          # (B, 1, H)
    li, lf = log_i[:, 0], log_f[:, 0]             # (B, H)

    m_new = jnp.maximum(state.m + lf, li)
    w_old = jnp.exp(state.m + lf - m_new)
    w_in = jnp.exp(li - m_new)
    C = w_old[..., None, None] * state.C + \
        w_in[..., None, None] * jnp.einsum("bhd,bhe->bhde",
                                           k.astype(jnp.float32),
                                           v.astype(jnp.float32))
    n = w_old[..., None] * state.n + w_in[..., None] * k.astype(jnp.float32)
    num = jnp.einsum("bhd,bhde->bhe", q.astype(jnp.float32), C)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh",
                                         q.astype(jnp.float32), n)), 1.0)
    h = (num / den[..., None]).reshape(B, 1, d)
    h = _group_rmsnorm(h, p["ln"], H)
    out = (h.astype(x.dtype) @ p["wo"]).astype(x.dtype)  # bf16 pre-AR (SSPerf)
    return out, MLSTMState(C=C, n=n, m=m_new)


# ===========================================================================
# sLSTM (scalar memory, exponential gating, head-wise state mixing)
# ===========================================================================

def init_slstm(cfg: ArchConfig, rng: Array, dtype) -> dict:
    d = cfg.d_model
    H = cfg.mlstm_heads or cfg.n_heads
    hd = d // H
    k = jax.random.split(rng, 3)
    s = d ** -0.5
    return {
        # 4 gates (z, i, f, o) from input...
        "w": (jax.random.normal(k[0], (d, 4 * d)) * s).astype(dtype),
        # ...and block-diagonal recurrence per head.
        "r": (jax.random.normal(k[1], (H, hd, 4 * hd)) * hd ** -0.5
              ).astype(dtype),
        "b": jnp.concatenate([jnp.zeros((2 * d,)), 3.0 * jnp.ones((d,)),
                              jnp.zeros((d,))]).astype(jnp.float32),
        "wo": (jax.random.normal(k[2], (d, d)) * s).astype(dtype),
        "ln": jnp.ones((d,), jnp.float32),
    }


class SLSTMState(NamedTuple):
    c: Array   # (B, d) cell
    n: Array   # (B, d) normalizer
    h: Array   # (B, d) hidden
    m: Array   # (B, d) stabilizer


def slstm_init_state(cfg: ArchConfig, B: int, dtype=jnp.float32) -> SLSTMState:
    d = cfg.d_model
    z = jnp.zeros((B, d), dtype)
    return SLSTMState(c=z, n=z, h=z, m=jnp.full((B, d), -1e30, dtype))


def _slstm_step(cfg: ArchConfig, p: dict, state: SLSTMState,
                xt: Array) -> tuple[SLSTMState, Array]:
    """xt: (B, d) -> (new_state, h_out (B, d))."""
    B, d = xt.shape
    H = cfg.mlstm_heads or cfg.n_heads
    hd = d // H
    hh = state.h.reshape(B, H, hd)
    rec = jnp.einsum("bhi,hio->bho", hh.astype(p["r"].dtype), p["r"])
    g = (xt @ p["w"]).astype(jnp.float32) + \
        rec.reshape(B, 4 * d).astype(jnp.float32) + p["b"]
    zt = jnp.tanh(g[:, :d])
    it = g[:, d:2 * d]                       # log-space input gate
    ft = jax.nn.log_sigmoid(g[:, 2 * d:3 * d])
    ot = jax.nn.sigmoid(g[:, 3 * d:])
    m_new = jnp.maximum(state.m + ft, it)
    w_old = jnp.exp(state.m + ft - m_new)
    w_in = jnp.exp(it - m_new)
    c = w_old * state.c + w_in * zt
    n = w_old * state.n + w_in
    h = ot * c / jnp.maximum(n, 1.0)
    return SLSTMState(c=c, n=n, h=h, m=m_new), h


def _slstm_impl(cfg: ArchConfig, p: dict, x: Array,
                return_state: bool = False):
    B, T, d = x.shape
    state0 = slstm_init_state(cfg, B)

    def step(s, xt):
        s2, h = _slstm_step(cfg, p, s, xt)
        return s2, h

    final, hs = jax.lax.scan(step, state0, x.swapaxes(0, 1))
    h = hs.swapaxes(0, 1)                                   # (B, T, d)
    h = _group_rmsnorm(h, p["ln"], cfg.mlstm_heads or cfg.n_heads)
    out = (h.astype(x.dtype) @ p["wo"]).astype(x.dtype)  # bf16 pre-AR (SSPerf)
    if return_state:
        return out, final
    return out


def slstm(cfg: ArchConfig, p: dict, x: Array, return_state: bool = False,
          *, mesh=None, batch_axes=()):
    """Sequential scan over T (sLSTM mixes state across time — no parallel
    form exists; xLSTM uses few sLSTM blocks for exactly this reason).

    With a mesh, the scan runs inside a shard_map island: inputs stay
    batch-sharded, weights replicated, and the recurrent-weight gradient is
    psum'd ONCE at the island boundary. Under plain pjit, GSPMD instead
    re-reduces the replicated dW at EVERY timestep of the bwd scan
    (97 GB/step on xlstm train — EXPERIMENTS.md SSPerf xlstm entry)."""
    if mesh is None or not batch_axes:
        return _slstm_impl(cfg, p, x, return_state)

    from jax.sharding import PartitionSpec as P

    ms = dict(zip(mesh.axis_names, mesh.devices.shape))
    B = x.shape[0]
    axes = tuple(batch_axes)
    while axes:
        n = 1
        for a in axes:
            n *= ms[a]
        if B % n == 0:
            break
        axes = axes[:-1]
    if not axes:
        return _slstm_impl(cfg, p, x, return_state)

    bspec = P(axes, None, None)
    wspec = jax.tree.map(lambda _: P(), p)
    sspec = SLSTMState(*(P(axes, None),) * 4)
    out_specs = (bspec, sspec) if return_state else bspec

    def body(xl, pl_):
        return _slstm_impl(cfg, pl_, xl, return_state)

    fn = jax.shard_map(body, mesh=mesh, in_specs=(bspec, wspec),
                       out_specs=out_specs, check_vma=False)
    return fn(x, p)


def slstm_decode(cfg: ArchConfig, p: dict, x: Array,
                 state: SLSTMState) -> tuple[Array, SLSTMState]:
    s2, h = _slstm_step(cfg, p, state, x[:, 0])
    h = _group_rmsnorm(h[:, None], p["ln"], cfg.mlstm_heads or cfg.n_heads)
    return (h.astype(x.dtype) @ p["wo"]).astype(x.dtype), s2  # bf16 pre-AR


# ===========================================================================
# Mamba-style diagonal SSD (Hymba's SSM heads)
# ===========================================================================

def init_mamba(cfg: ArchConfig, rng: Array, dtype, d_inner: int) -> dict:
    d = cfg.d_model
    S = cfg.ssm_state
    H = d_inner // cfg.head_dim            # mamba heads, same head_dim
    k = jax.random.split(rng, 6)
    s = d ** -0.5
    return {
        "w_in": (jax.random.normal(k[0], (d, 2 * d_inner)) * s).astype(dtype),
        "w_bc": (jax.random.normal(k[1], (d, 2 * S)) * s).astype(dtype),
        "w_dt": (jax.random.normal(k[2], (d, H)) * s).astype(dtype),
        "b_dt": jnp.log(jnp.expm1(jnp.exp(jax.random.uniform(
            k[3], (H,), minval=jnp.log(0.001), maxval=jnp.log(0.1))))
        ).astype(jnp.float32),
        "A_log": jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)),
        "D": jnp.ones((H,), jnp.float32),
        "conv": (jax.random.normal(k[4], (4, d_inner)) * 0.5).astype(dtype),
        "w_out": (jax.random.normal(k[5], (d_inner, d)) *
                  d_inner ** -0.5).astype(dtype),
        "ln": jnp.ones((d_inner,), jnp.float32),
    }


class MambaState(NamedTuple):
    h: Array        # (B, H, hd, S) SSM state
    conv: Array     # (B, 3, d_inner) last inputs for the causal conv


def mamba_init_state(cfg: ArchConfig, B: int, d_inner: int,
                     dtype=jnp.float32) -> MambaState:
    H = d_inner // cfg.head_dim
    return MambaState(h=jnp.zeros((B, H, cfg.head_dim, cfg.ssm_state), dtype),
                      conv=jnp.zeros((B, 3, d_inner), dtype))


def _causal_conv(xc: Array, w: Array) -> Array:
    """Depthwise causal conv, window 4. xc (B, T, C), w (4, C)."""
    pad = jnp.pad(xc, ((0, 0), (3, 0), (0, 0)))
    out = sum(pad[:, i:i + xc.shape[1]] * w[i] for i in range(4))
    return out


def mamba(cfg: ArchConfig, p: dict, x: Array, d_inner: int,
          return_state: bool = False, project: bool = True):
    """Full-sequence SSD via associative scan. x: (B, T, d).

    project=False returns the gated pre-projection activations so hybrid
    blocks can FUSE the mamba out-projection with the attention wo into one
    partial-sum dot -> one TP all-reduce (EXPERIMENTS.md SSPerf hymba 3b).
    """
    B, T, d = x.shape
    hd = cfg.head_dim
    H = d_inner // hd
    S = cfg.ssm_state

    xz = x @ p["w_in"]
    xc, z = xz[..., :d_inner], xz[..., d_inner:]
    xc = jax.nn.silu(_causal_conv(xc, p["conv"]))
    bc = x @ p["w_bc"]
    Bm, Cm = bc[..., :S], bc[..., S:]                   # (B, T, S)
    dt = jax.nn.softplus((x @ p["w_dt"]).astype(jnp.float32) + p["b_dt"])
    A = -jnp.exp(p["A_log"])                            # (H,) negative
    decay = jnp.exp(dt * A)                             # (B, T, H)

    xh = xc.reshape(B, T, H, hd).astype(jnp.float32)

    # Chunked scan: the (B, T, H, hd, S) state sequence would be ~16x the
    # activation size; scanning CHUNK-sized windows with an intra-chunk
    # associative scan keeps the state working set to one chunk.
    W = min(CHUNK, T)
    W = W if T % W == 0 else math.gcd(T, W)
    nc = T // W

    def combine(a, b):
        d1, s1 = a
        d2, s2 = b
        return d1 * d2, s1 * d2 + s2

    def chunk_body(h_in, inp_c):
        dt_c, xh_c, B_c, C_c, dec_c = inp_c            # (B, W, ...) leading
        inp = jnp.einsum("bth,bthd,bts->bthds", dt_c, xh_c, B_c)
        dec = dec_c[..., None, None]                   # (B, W, H, 1, 1)
        cumdec, hwithin = jax.lax.associative_scan(combine, (dec, inp),
                                                   axis=1)
        h_t = cumdec * h_in[:, None] + hwithin         # (B, W, H, hd, S)
        y_c = jnp.einsum("bthds,bts->bthd", h_t, C_c)
        return h_t[:, -1], y_c

    xs = tuple(jnp.moveaxis(a.reshape(B, nc, W, *a.shape[2:]), 1, 0)
               for a in (dt, xh, Bm.astype(jnp.float32),
                         Cm.astype(jnp.float32), decay))
    h0 = jnp.zeros((B, H, hd, S), jnp.float32)
    h_final, ys = jax.lax.scan(chunk_body, h0, xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(B, T, H, hd)
    y = y + xh * p["D"][None, None, :, None]
    y = y.reshape(B, T, d_inner)
    y = _group_rmsnorm(y, p["ln"], H)
    y = y * jax.nn.silu(z)
    # Cast BEFORE the row-parallel out projection: GSPMD all-reduces the
    # partial dot output, and a f32 partial doubles TP collective bytes
    # (EXPERIMENTS.md SSPerf hymba iteration 3).
    out = y.astype(x.dtype) if not project else \
        (y.astype(x.dtype) @ p["w_out"]).astype(x.dtype)
    if return_state:
        xc_raw = xz[..., :d_inner]                      # pre-conv inputs
        pad = jnp.concatenate([jnp.zeros((B, 3, d_inner), xc_raw.dtype),
                               xc_raw], axis=1)
        state = MambaState(h=h_final, conv=pad[:, T:T + 3])
        return out, state
    return out


def mamba_decode(cfg: ArchConfig, p: dict, x: Array, state: MambaState,
                 d_inner: int) -> tuple[Array, MambaState]:
    """One-token step. x: (B, 1, d)."""
    B, _, d = x.shape
    hd = cfg.head_dim
    H = d_inner // hd
    S = cfg.ssm_state

    xz = x[:, 0] @ p["w_in"]
    xc_t, z = xz[..., :d_inner], xz[..., d_inner:]
    window = jnp.concatenate([state.conv, xc_t[:, None]], axis=1)  # (B,4,di)
    xc = jax.nn.silu(jnp.einsum("bkc,kc->bc", window.astype(jnp.float32),
                                p["conv"].astype(jnp.float32)))
    bc = x[:, 0] @ p["w_bc"]
    Bm, Cm = bc[..., :S], bc[..., S:]
    dt = jax.nn.softplus((x[:, 0] @ p["w_dt"]).astype(jnp.float32) + p["b_dt"])
    A = -jnp.exp(p["A_log"])
    decay = jnp.exp(dt * A)                             # (B, H)

    xh = xc.reshape(B, H, hd).astype(jnp.float32)
    inp = jnp.einsum("bh,bhd,bs->bhds", dt, xh, Bm.astype(jnp.float32))
    h = state.h * decay[..., None, None] + inp
    y = jnp.einsum("bhds,bs->bhd", h, Cm.astype(jnp.float32))
    y = y + xh * p["D"][None, :, None]
    y = y.reshape(B, 1, d_inner)
    y = _group_rmsnorm(y, p["ln"], H)
    y = y * jax.nn.silu(z)[:, None]
    out = (y @ p["w_out"]).astype(x.dtype)
    return out, MambaState(h=h, conv=window[:, 1:])
