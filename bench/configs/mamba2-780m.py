"""Plain reference of Mamba-2 (Dao & Gu, arXiv:2405.21060) as configured in
``mamba2-780m.json``: its weights made from a seed, and its training loss in
float32 at the highest matmul precision, written from the published
description with nothing taken from the program under test.

The SSD mixer is computed in its quadratic ("attention") form over the
whole sequence, ``y_t = sum_{s<=t} C_t.B_s exp(sum_{r=s+1..t} dt_r A) dt_s
x_s + D x_t``, which is the definition the chunked algorithm computes.
Parameters are laid out as the program lays them out (a pytree of named
leaves, the 48 layers stacked on a leading axis), so that the harness can
hand the same weights to both.

``mm`` is the one place where operands meet a matrix unit; the control
replaces its ``cast`` to compute in a lower precision.
"""

from __future__ import annotations

import dataclasses
import math
import zlib

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def dims(conf: dict) -> dict:
    layer = conf["mamba2_layer"]
    d = conf["d_model"]
    di = layer["expand"] * d
    return dict(
        D=d, L=conf["n_layer"], V=conf["vocab_size"], di=di,
        H=di // layer["headdim"], P=layer["headdim"], N=layer["d_state"],
        G=layer["ngroups"], K=layer["d_conv"], eps=conf["assumed"]["norm_epsilon"],
    )


def program_config(conf: dict, archs):
    """The program's ModelConfig, set from this file's numbers."""
    cfg = archs.get(conf["program_arch"])
    layer, a = conf["mamba2_layer"], conf["assumed"]
    if not (cfg.attn_free and cfg.no_ffn and conf["tie_embeddings"]):
        raise ValueError(f"{conf['program_arch']} is not a tied attention-free Mamba-2")
    mamba = dataclasses.replace(
        cfg.mamba, d_state=layer["d_state"], d_conv=layer["d_conv"],
        expand=layer["expand"], head_dim=layer["headdim"],
        n_groups=layer["ngroups"], chunk=layer["chunk_size"],
        dt_min=layer["dt_min"], dt_max=layer["dt_max"],
    )
    return cfg.replace(
        d_model=conf["d_model"], n_layers=conf["n_layer"],
        vocab_size=conf["vocab_size"], mamba=mamba,
        param_dtype=a["param_dtype"], compute_dtype=a["compute_dtype"],
        opt_dtype=a["opt_dtype"], norm_eps=a["norm_epsilon"],
    )


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def _uniform(key, path, shape, bound):
    return jax.random.uniform(_key(key, path), shape, jnp.float32, -bound, bound)


def init(key, conf: dict) -> dict:
    """Initial weights from ``key`` (see the file's ``assumed.init``)."""
    d = dims(conf)
    layer = conf["mamba2_layer"]
    D, L, di, H, N, G, K = d["D"], d["L"], d["di"], d["H"], d["N"], d["G"], d["K"]
    u = lambda path, shape, bound: _uniform(key, path, shape, bound)
    lo, hi = math.log(layer["dt_min"]), math.log(layer["dt_max"])
    dt = jnp.exp(jax.random.uniform(_key(key, "dt"), (L, H)) * (hi - lo) + lo)
    dt = jnp.maximum(dt, layer["dt_init_floor"])
    a_lo, a_hi = layer["A_init_range"]
    mamba = {
        "wz": u("wz", (L, D, di), D ** -0.5),
        "wx": u("wx", (L, D, di), D ** -0.5),
        "wB": u("wB", (L, D, G, N), D ** -0.5),
        "wC": u("wC", (L, D, G, N), D ** -0.5),
        "wdt": u("wdt", (L, D, H), D ** -0.5),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "conv_wx": u("conv_wx", (L, K, di), K ** -0.5),
        "conv_bx": u("conv_bx", (L, di), K ** -0.5),
        "conv_wB": u("conv_wB", (L, K, G * N), K ** -0.5),
        "conv_bB": u("conv_bB", (L, G * N), K ** -0.5),
        "conv_wC": u("conv_wC", (L, K, G * N), K ** -0.5),
        "conv_bC": u("conv_bC", (L, G * N), K ** -0.5),
        "A_log": jnp.log(jax.random.uniform(_key(key, "A"), (L, H), jnp.float32, a_lo, a_hi)),
        "D_skip": jnp.ones((L, H), jnp.float32),
        "norm": jnp.zeros((L, di), jnp.float32),
        "out": u("out", (L, di, D), di ** -0.5) / math.sqrt(L),
    }
    return {
        "embed": {"tok": 0.02 * jax.random.normal(_key(key, "tok"), (d["V"], D))},
        "final_ln": jnp.zeros((D,), jnp.float32),
        "units": {"L0": {"ln": jnp.zeros((L, D), jnp.float32), "mamba": mamba}},
    }


# ---------------------------------------------------------------------------
# forward and loss
# ---------------------------------------------------------------------------

def mm(spec, a, b, cast):
    return jnp.einsum(spec, cast(a), cast(b), precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def rmsnorm(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return x * (1.0 + scale)


def causal_conv(x, w, b):
    """Depthwise causal conv: out_t = sum_i x_{t-K+1+i} w_i + b."""
    K, S = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(xp[:, i:i + S] * w[i] for i in range(K)) + b


def ssd(x, dt, A, Bm, Cm, cast):
    """Quadratic SSD. x (B,S,H,P), dt (B,S,H), A (H,), Bm/Cm (B,S,N)."""
    S = x.shape[1]
    cum = jnp.cumsum(dt * A, axis=1)                          # (B,S,H)
    diff = cum[:, :, None, :] - cum[:, None, :, :]            # (B,t,s,H)
    causal = jnp.tril(jnp.ones((S, S), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    cb = mm("btn,bsn->bts", Cm, Bm, cast)                     # (B,t,s)
    w = cb[..., None] * decay * dt[:, None, :, :]             # (B,t,s,H)
    return mm("btsh,bshp->bthp", w, x, cast)


def mixer(p, h, d, cast):
    Bsz, S, _ = h.shape
    H, P, N, G = d["H"], d["P"], d["N"], d["G"]
    z = mm("bsd,di->bsi", h, p["wz"], cast)
    xc = mm("bsd,di->bsi", h, p["wx"], cast)
    Bv = mm("bsd,dgn->bsgn", h, p["wB"], cast).reshape(Bsz, S, G * N)
    Cv = mm("bsd,dgn->bsgn", h, p["wC"], cast).reshape(Bsz, S, G * N)
    dt = jax.nn.softplus(mm("bsd,dh->bsh", h, p["wdt"], cast) + p["dt_bias"])
    xc = jax.nn.silu(causal_conv(xc, p["conv_wx"], p["conv_bx"]))
    Bv = jax.nn.silu(causal_conv(Bv, p["conv_wB"], p["conv_bB"]))
    Cv = jax.nn.silu(causal_conv(Cv, p["conv_wC"], p["conv_bC"]))
    if G != 1:
        raise NotImplementedError("the reference is written for one B/C group")
    xh = xc.reshape(Bsz, S, H, P)
    y = ssd(xh, dt, -jnp.exp(p["A_log"]), Bv, Cv, cast)
    y = (y + xh * p["D_skip"][:, None]).reshape(Bsz, S, d["di"])
    y = rmsnorm(y * jax.nn.silu(z), p["norm"], d["eps"])
    return mm("bsi,id->bsd", y, p["out"], cast)


def loss(params, batch, conf, cast=lambda a: a):
    """Token-mean cross-entropy of one node's batch (B,S) in float32."""
    d = dims(conf)
    h = params["embed"]["tok"][batch["tokens"]]

    @jax.checkpoint
    def layer(h, p):
        return h + mixer(p["mamba"], rmsnorm(h, p["ln"], d["eps"]), d, cast), None

    h, _ = jax.lax.scan(layer, h, params["units"]["L0"])
    h = rmsnorm(h, params["final_ln"], d["eps"])
    logits = mm("bsd,vd->bsv", h, params["embed"]["tok"], cast)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["labels"][..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)
