"""Mamba-2 (SSD — state-space duality) mixer, chunked, in pure JAX.

The chunked SSD algorithm (Dao & Gu, arXiv:2405.21060) splits the sequence
into MXU-friendly chunks: inside a chunk the recurrence is computed as
attention-like matmuls against the decay kernel L; across chunks a small
recurrent state (B, H, P, N) is carried by ``lax.scan``. This is both the
memory-sane XLA path and the exact structure of the Pallas kernel
(:mod:`repro.kernels.ssd_scan`); the sequential-scan oracle lives in
``kernels/ssd_scan/ref.py``.

Layout: x (B,S,D) -> z,xc (B,S,di), B,C (B,S,G,N), dt (B,S,Hm);
heads Hm = di / P share B/C within each of the G groups, and the gated
RMSNorm before the out-projection normalises each group's di / G channels
on their own.
"""

from __future__ import annotations

import math
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.launch.sharding import shard_activation
from repro.models.config import ModelConfig
from repro.models.layers import dtype_of, rmsnorm, truncated_normal


# Name of the values a training layer keeps for its backward: the
# in-projection outputs (z, x, B, C, dt) and the SSD scan's output y. The
# layer checkpoint of ``transformer.forward_train`` saves the values
# carrying it, so the backward reads them instead of recomputing the
# projection matmuls and the scan's quadratic chunk work; the scan's own
# backward recomputes each chunk once (``ssd_chunked(remat_body=True)``).
# Outside a checkpoint (serving) the name is the identity.
SAVED = "mamba_saved"


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def init_mamba(key, cfg: ModelConfig) -> Tuple[Dict, Dict]:
    mb = cfg.mamba
    D = cfg.d_model
    di = mb.d_inner(D)
    Hm = mb.n_heads(D)
    G, N, K = mb.n_groups, mb.d_state, mb.d_conv
    dt = dtype_of(cfg.param_dtype)
    ks = jax.random.split(key, 8)
    std = D ** -0.5

    # dt bias: inverse-softplus of dt sampled log-uniform in [dt_min, dt_max]
    u = jax.random.uniform(ks[6], (Hm,))
    dt_init = jnp.exp(
        u * (math.log(mb.dt_max) - math.log(mb.dt_min)) + math.log(mb.dt_min)
    )
    dt_bias = dt_init + jnp.log(-jnp.expm1(-dt_init))  # softplus^-1

    p = {
        "wz": truncated_normal(ks[0], (D, di), std, dt),
        "wx": truncated_normal(ks[1], (D, di), std, dt),
        "wB": truncated_normal(ks[2], (D, G, N), std, dt),
        "wC": truncated_normal(ks[3], (D, G, N), std, dt),
        "wdt": truncated_normal(ks[4], (D, Hm), std, dt),
        "dt_bias": dt_bias.astype(jnp.float32),
        # separate depthwise convs per stream (x / B / C): mathematically
        # identical to the joint conv over concat([x,B,C]) but keeps each
        # stream's sharding intact (concat+slice across a model-sharded dim
        # would force GSPMD reshards — see DESIGN.md §3 adaptation notes).
        "conv_wx": truncated_normal(ks[5], (K, di), di ** -0.5, dt),
        "conv_bx": jnp.zeros((di,), dtype=dt),
        "conv_wB": truncated_normal(jax.random.fold_in(ks[5], 1), (K, G * N), (G * N) ** -0.5, dt),
        "conv_bB": jnp.zeros((G * N,), dtype=dt),
        "conv_wC": truncated_normal(jax.random.fold_in(ks[5], 2), (K, G * N), (G * N) ** -0.5, dt),
        "conv_bC": jnp.zeros((G * N,), dtype=dt),
        "A_log": jnp.log(
            jax.random.uniform(ks[7], (Hm,), minval=1.0, maxval=16.0)
        ).astype(jnp.float32),
        "D_skip": jnp.ones((Hm,), dtype=jnp.float32),
        "norm": jnp.zeros((di,), dtype=dt),
        "out": truncated_normal(jax.random.fold_in(key, 99), (di, D), di ** -0.5, dt),
    }
    s = {
        "wz": ("embed", "mamba_inner"),
        "wx": ("embed", "mamba_inner"),
        "wB": ("embed", "groups", "state"),
        "wC": ("embed", "groups", "state"),
        "wdt": ("embed", "mamba_heads"),
        "dt_bias": ("mamba_heads",),
        "conv_wx": ("conv_k", "mamba_inner"),
        "conv_bx": ("mamba_inner",),
        "conv_wB": ("conv_k", None),
        "conv_bB": (None,),
        "conv_wC": ("conv_k", None),
        "conv_bC": (None,),
        "A_log": ("mamba_heads",),
        "D_skip": ("mamba_heads",),
        "norm": ("mamba_inner",),
        "out": ("mamba_inner", "embed"),
    }
    return p, s


def gated_norm(y: jax.Array, z: jax.Array, scale: jax.Array, cfg: ModelConfig) -> jax.Array:
    """RMSNorm of ``y * silu(z)`` over each of the G groups of channels
    (norm after the gate, as Mamba-2's ``RMSNormGated`` with
    ``group_size = d_inner / n_groups``); one group is a plain RMSNorm."""
    G = cfg.mamba.n_groups
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    if G == 1:
        return rmsnorm(y, scale, cfg.norm_eps)
    shape, dt = y.shape, y.dtype
    g = y.astype(jnp.float32).reshape(shape[:-1] + (G, shape[-1] // G))
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True) + cfg.norm_eps)
    return (g.reshape(shape) * (1.0 + scale.astype(jnp.float32))).astype(dt)


# ---------------------------------------------------------------------------
# causal depthwise conv
# ---------------------------------------------------------------------------

def causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """x: (B,S,C); w: (K,C) depthwise. Left-padded causal convolution."""
    K = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(
        xp[:, i : i + x.shape[1], :] * w[i][None, None, :] for i in range(K)
    )
    return out + b[None, None, :]


def conv_step(x_t: jax.Array, conv_state: jax.Array, w: jax.Array, b: jax.Array):
    """Single decode step. x_t: (B,C); conv_state: (B,K-1,C). Returns
    (out (B,C), new_state)."""
    window = jnp.concatenate([conv_state, x_t[:, None, :]], axis=1)  # (B,K,C)
    out = jnp.einsum("bkc,kc->bc", window, w) + b[None, :]
    return out, window[:, 1:, :]


# ---------------------------------------------------------------------------
# chunked SSD forward (train / prefill)
# ---------------------------------------------------------------------------

class MambaCache(NamedTuple):
    ssm: jax.Array        # (B, Hm, P, N) fp32 recurrent state
    conv: jax.Array       # (B, K-1, conv_dim)


def _project(p: Dict, x: jax.Array, cfg: ModelConfig):
    cdt = x.dtype
    z = jnp.einsum("bsd,di->bsi", x, p["wz"].astype(cdt))
    xc = jnp.einsum("bsd,di->bsi", x, p["wx"].astype(cdt))
    Bv = jnp.einsum("bsd,dgn->bsgn", x, p["wB"].astype(cdt))
    Cv = jnp.einsum("bsd,dgn->bsgn", x, p["wC"].astype(cdt))
    dt_raw = jnp.einsum("bsd,dh->bsh", x, p["wdt"].astype(cdt))
    z = shard_activation(z, ("batch", "seq", "mamba_inner"))
    xc = shard_activation(xc, ("batch", "seq", "mamba_inner"))
    Bv = shard_activation(Bv, ("batch", "seq", None, None))
    Cv = shard_activation(Cv, ("batch", "seq", None, None))
    dt_raw = shard_activation(dt_raw, ("batch", "seq", "mamba_heads"))
    return tuple(checkpoint_name(t, SAVED) for t in (z, xc, Bv, Cv, dt_raw))


def _conv_mix(p, xc, Bv, Cv, cfg: ModelConfig):
    """Per-stream causal convs (x / B / C) then SiLU (see init_mamba note)."""
    B_, S = xc.shape[:2]
    mb = cfg.mamba
    G, N = mb.n_groups, mb.d_state
    cdt = xc.dtype
    xc = jax.nn.silu(causal_conv(xc, p["conv_wx"].astype(cdt), p["conv_bx"].astype(cdt)))
    Bf = jax.nn.silu(causal_conv(
        Bv.reshape(B_, S, G * N), p["conv_wB"].astype(cdt), p["conv_bB"].astype(cdt)
    ))
    Cf = jax.nn.silu(causal_conv(
        Cv.reshape(B_, S, G * N), p["conv_wC"].astype(cdt), p["conv_bC"].astype(cdt)
    ))
    xc = shard_activation(xc, ("batch", "seq", "mamba_inner"))
    return xc, Bf.reshape(B_, S, G, N), Cf.reshape(B_, S, G, N)


def _expand_groups(t: jax.Array, Hm: int) -> jax.Array:
    """(B,Q,G,N) -> (B,Q,Hm,N) by broadcasting each group over its heads."""
    B_, Q, G, N = t.shape
    r = Hm // G
    return jnp.broadcast_to(t[:, :, :, None, :], (B_, Q, G, r, N)).reshape(
        B_, Q, Hm, N
    )


def ssd_chunked(
    xh: jax.Array,      # (B, S, Hm, P)
    dt: jax.Array,      # (B, S, Hm) fp32 (post softplus)
    A: jax.Array,       # (Hm,) fp32 negative
    Bv: jax.Array,      # (B, S, G, N)
    Cv: jax.Array,      # (B, S, G, N)
    chunk: int,
    init_state: Optional[jax.Array] = None,
    remat_body: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD scan. Returns (y (B,S,Hm,P), final_state (B,Hm,P,N))."""
    B_, S, Hm, P = xh.shape
    G, N = Bv.shape[2], Bv.shape[3]
    nc = S // chunk
    assert nc * chunk == S, (S, chunk)

    xh_c = xh.reshape(B_, nc, chunk, Hm, P)
    dt_c = dt.reshape(B_, nc, chunk, Hm)
    Bv_c = Bv.reshape(B_, nc, chunk, G, N)
    Cv_c = Cv.reshape(B_, nc, chunk, G, N)

    def body(state, inputs):
        xq, dtq, Bq, Cq = inputs          # (B,Q,H,P), (B,Q,H), (B,Q,G,N) x2
        state = shard_activation(state, ("batch", "mamba_heads", None, None))
        xq = shard_activation(xq, ("batch", None, "mamba_heads", None))
        dtq = shard_activation(dtq, ("batch", None, "mamba_heads"))
        Bh = _expand_groups(Bq, Hm)       # (B,Q,H,N)
        Ch = _expand_groups(Cq, Hm)
        Bh = shard_activation(Bh, ("batch", None, "mamba_heads", None))
        Ch = shard_activation(Ch, ("batch", None, "mamba_heads", None))
        l = dtq * A[None, None, :]        # (B,Q,H) negative decays
        cum = jnp.cumsum(l, axis=1)       # inclusive within-chunk cumsum
        decay_chunk = jnp.exp(cum[:, -1])                      # (B,H)
        # inter-chunk: Y_t += exp(cum_t) * C_t . S_prev
        y_inter = jnp.einsum(
            "bqhn,bhpn->bqhp", Ch.astype(jnp.float32), state
        ) * jnp.exp(cum)[..., None]
        # intra-chunk: W[t,s] = (C_t.B_s) exp(cum_t - cum_s) dt_s for s<=t
        CB = jnp.einsum(
            "bqhn,bshn->bhqs", Ch, Bh, preferred_element_type=jnp.float32
        )
        cum_t = cum.transpose(0, 2, 1)    # (B,H,Q)
        tri = jnp.tril(jnp.ones((chunk, chunk), dtype=bool))
        # mask the exponent, not the product: for s > t, cum_t - cum_s is a
        # growing positive sum that overflows exp() in long chunks, and the
        # masked inf would turn the backward pass into inf * 0 = NaN
        Ldec = jnp.exp(jnp.where(
            tri[None, None], cum_t[:, :, :, None] - cum_t[:, :, None, :], -jnp.inf
        ))
        W = jnp.where(tri[None, None], CB * Ldec, 0.0)
        W = W * dtq.transpose(0, 2, 1)[:, :, None, :]          # weight dt_s
        y_intra = jnp.einsum(
            "bhqs,bshp->bqhp", W.astype(xq.dtype), xq,
            preferred_element_type=jnp.float32,
        )
        # state update: S = decay_chunk*S + sum_s exp(cum_Q - cum_s) dt_s B_s x_s
        decay_to_end = jnp.exp(cum[:, -1:, :] - cum) * dtq     # (B,Q,H)
        dB = Bh.astype(jnp.float32) * decay_to_end[..., None]  # (B,Q,H,N)
        new_state = decay_chunk[:, :, None, None] * state + jnp.einsum(
            "bqhn,bqhp->bhpn", dB, xh_f32(xq)
        )
        new_state = shard_activation(new_state, ("batch", "mamba_heads", None, None))
        y = (y_inter + y_intra).astype(xq.dtype)
        y = shard_activation(y, ("batch", None, "mamba_heads", None))
        return new_state, y

    def xh_f32(t):
        return t.astype(jnp.float32)

    if init_state is None:
        init_state = jnp.zeros((B_, Hm, P, N), dtype=jnp.float32)
    fn = jax.checkpoint(body) if remat_body else body
    final_state, ys = jax.lax.scan(
        fn,
        init_state,
        (
            xh_c.transpose(1, 0, 2, 3, 4),
            dt_c.transpose(1, 0, 2, 3),
            Bv_c.transpose(1, 0, 2, 3, 4),
            Cv_c.transpose(1, 0, 2, 3, 4),
        ),
    )
    y = ys.transpose(1, 0, 2, 3, 4).reshape(B_, S, Hm, P)
    return y, final_state


def mamba_forward(p: Dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Training mixer: project -> conv -> SSD -> gate -> out. x: (B,S,D)."""
    mb = cfg.mamba
    D = cfg.d_model
    di, Hm = mb.d_inner(D), mb.n_heads(D)
    P = mb.head_dim
    B_, S, _ = x.shape

    z, xc, Bv, Cv, dt_raw = _project(p, x, cfg)
    xc, Bv, Cv = _conv_mix(p, xc, Bv, Cv, cfg)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"][None, None])
    A = -jnp.exp(p["A_log"])
    xh = xc.reshape(B_, S, Hm, P)

    chunk = min(mb.chunk, S)
    y, _ = ssd_chunked(
        xh, dt, A, Bv, Cv, chunk, remat_body=cfg.remat != "none"
    )
    y = checkpoint_name(y, SAVED)
    y = y + xh * p["D_skip"][None, None, :, None].astype(y.dtype)
    y = y.reshape(B_, S, di)
    y = gated_norm(y, z, p["norm"], cfg)
    return jnp.einsum("bsi,id->bsd", y, p["out"].astype(y.dtype))


def mamba_prefill(p: Dict, x: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, MambaCache]:
    """Prefill: like forward but also returns the true conv tail state."""
    mb = cfg.mamba
    D = cfg.d_model
    di, Hm = mb.d_inner(D), mb.n_heads(D)
    P, N, G = mb.head_dim, mb.d_state, mb.n_groups
    B_, S, _ = x.shape
    z, xc0, Bv0, Cv0, dt_raw = _project(p, x, cfg)
    # decode conv state: last K-1 PRE-conv inputs, concat layout [x|B|C]
    cat = jnp.concatenate(
        [xc0, Bv0.reshape(B_, S, G * N), Cv0.reshape(B_, S, G * N)], axis=-1
    )
    K = mb.d_conv
    conv_tail = cat[:, S - (K - 1) :, :]
    xc, Bv, Cv = _conv_mix(p, xc0, Bv0, Cv0, cfg)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"][None, None])
    A = -jnp.exp(p["A_log"])
    xh = xc.reshape(B_, S, Hm, P)
    y, final_state = ssd_chunked(xh, dt, A, Bv, Cv, min(mb.chunk, S))
    y = y + xh * p["D_skip"][None, None, :, None].astype(y.dtype)
    y = y.reshape(B_, S, di)
    y = gated_norm(y, z, p["norm"], cfg)
    out = jnp.einsum("bsi,id->bsd", y, p["out"].astype(y.dtype))
    return out, MambaCache(ssm=final_state, conv=conv_tail)


def mamba_decode_step(
    p: Dict, x_t: jax.Array, cache: MambaCache, cfg: ModelConfig
) -> Tuple[jax.Array, MambaCache]:
    """One recurrent step. x_t: (B,1,D) -> (B,1,D)."""
    mb = cfg.mamba
    D = cfg.d_model
    di, Hm = mb.d_inner(D), mb.n_heads(D)
    P, N, G = mb.head_dim, mb.d_state, mb.n_groups
    B_ = x_t.shape[0]
    z, xc, Bv, Cv, dt_raw = _project(p, x_t, cfg)
    cat = jnp.concatenate(
        [xc[:, 0], Bv.reshape(B_, 1, G * N)[:, 0], Cv.reshape(B_, 1, G * N)[:, 0]],
        axis=-1,
    )
    window = jnp.concatenate([cache.conv, cat[:, None, :]], axis=1)  # (B,K,C)
    new_conv = window[:, 1:, :]
    # per-stream convs applied to the shared [x|B|C] window
    wx = window[..., :di]
    wB = window[..., di : di + G * N]
    wC = window[..., di + G * N :]
    xc = jax.nn.silu(
        jnp.einsum("bkc,kc->bc", wx, p["conv_wx"].astype(cat.dtype))
        + p["conv_bx"].astype(cat.dtype)[None]
    )
    Bv = jax.nn.silu(
        jnp.einsum("bkc,kc->bc", wB, p["conv_wB"].astype(cat.dtype))
        + p["conv_bB"].astype(cat.dtype)[None]
    ).reshape(B_, G, N)
    Cv = jax.nn.silu(
        jnp.einsum("bkc,kc->bc", wC, p["conv_wC"].astype(cat.dtype))
        + p["conv_bC"].astype(cat.dtype)[None]
    ).reshape(B_, G, N)
    dt = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32) + p["dt_bias"][None])
    A = -jnp.exp(p["A_log"])

    xh = xc.reshape(B_, Hm, P)
    r = Hm // G
    Bh = jnp.broadcast_to(Bv[:, :, None, :], (B_, G, r, N)).reshape(B_, Hm, N)
    Ch = jnp.broadcast_to(Cv[:, :, None, :], (B_, G, r, N)).reshape(B_, Hm, N)
    decay = jnp.exp(dt * A[None])                                  # (B,H)
    new_ssm = decay[:, :, None, None] * cache.ssm + jnp.einsum(
        "bhn,bhp,bh->bhpn", Bh.astype(jnp.float32), xh.astype(jnp.float32), dt
    )
    y = jnp.einsum("bhn,bhpn->bhp", Ch.astype(jnp.float32), new_ssm)
    y = y + xh.astype(jnp.float32) * p["D_skip"][None, :, None]
    y = y.reshape(B_, 1, di).astype(x_t.dtype)
    y = gated_norm(y, z, p["norm"], cfg)
    out = jnp.einsum("bsi,id->bsd", y, p["out"].astype(y.dtype))
    return out, MambaCache(ssm=new_ssm, conv=new_conv)
