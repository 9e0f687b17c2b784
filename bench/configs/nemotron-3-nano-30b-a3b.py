"""Plain reference of NVIDIA Nemotron-3-Nano-30B-A3B (``nemotron_h``) as
configured in ``nemotron-3-nano-30b-a3b.json``: its weights made from a
seed, and its training loss in float32 at the highest matmul precision,
written from the published description with nothing taken from the
program under test.

The model is a stack of single-mixer layers, ``h + mixer(RMSNorm(h))``, in
the file's ``hybrid_override_pattern``:

- ``M``, Mamba-2: an in-projection to z, x, B, C (``n_groups`` groups) and
  dt; a causal depthwise conv with bias and SiLU over x, B and C; the SSD
  ``y_t = sum_{s<=t} C_t.B_s exp(sum_{r=s+1..t} dt_r A) dt_s x_s + D x_t``
  (each head reads its group's B and C); ``RMSNorm(y * silu(z))`` taken per
  group of ``d_inner / n_groups`` channels; the out-projection;
- ``E``, MoE: a float32 sigmoid router over all ``published.n_routed_experts``
  experts, the top ``num_experts_per_tok`` chosen by score plus the
  correction bias, their scores normalised to sum to 1 and scaled by
  ``routed_scaling_factor``; experts ``relu(x W_up)^2 W_down``; one shared
  expert of the same form added to every token;
- ``*``, attention: grouped-query, causal, softmax(Q K^T / sqrt(head_dim)) V,
  no bias and no positional encoding.

This chip holds ``n_routed_experts`` of the router's experts, from
``first_held``: the routed part of an MoE layer is the held experts'
contribution alone, as in the program (the model-configs guide's cut).

The SSD is computed in its quadratic form and attention from its scores,
both in blocks of query positions (``QUERY_BLOCK``) under a checkpoint, so
that a sequence of 8192 fits: a whole-sequence SSD would hold an (H, S, S)
float32 array of 17 GB. The MoE is a dense loop over the held experts, each
applied to every token with that token's routing weight (0 where it did not
choose the expert). Parameters are laid out as the program lays them out
(the pattern's layers ``L0..`` stacked on a leading unit axis), so the
harness hands the same weights to both.

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
QUERY_BLOCK = 256
ROW_BLOCK = 1  # the reference takes each sequence of a batch on its own


def dims(conf: dict) -> dict:
    di = conf["mamba_num_heads"] * conf["mamba_head_dim"]
    return dict(
        D=conf["hidden_size"], pattern=conf["hybrid_override_pattern"],
        L=conf["num_hidden_layers"], V=conf["vocab_size"], di=di,
        H=conf["mamba_num_heads"], P=conf["mamba_head_dim"], N=conf["ssm_state_size"],
        G=conf["n_groups"], K=conf["conv_kernel"],
        Hq=conf["num_attention_heads"], KV=conf["num_key_value_heads"], hd=conf["head_dim"],
        E=conf["published"]["n_routed_experts"], held=conf["n_routed_experts"],
        first=conf["first_held"], top_k=conf["num_experts_per_tok"],
        F=conf["moe_intermediate_size"],
        Fs=conf["moe_shared_expert_intermediate_size"] * conf["n_shared_experts"],
        scale=conf["routed_scaling_factor"], eps=conf["norm_eps"],
    )


def program_config(conf: dict, archs):
    """The program's ModelConfig, set from this file's numbers."""
    cfg = archs.get(conf["program_arch"])
    a = conf["assumed"]
    if not (cfg.layer_pattern and cfg.moe.dispatch == "dropless" and cfg.act == "relu2"
            and cfg.rope_theta is None and not conf["tie_word_embeddings"]):
        raise ValueError(f"{conf['program_arch']} is not a nemotron_h hybrid")
    mamba = dataclasses.replace(
        cfg.mamba, d_state=conf["ssm_state_size"], d_conv=conf["conv_kernel"],
        head_dim=conf["mamba_head_dim"], heads=conf["mamba_num_heads"],
        n_groups=conf["n_groups"], chunk=conf["chunk_size"],
        dt_min=conf["time_step_min"], dt_max=conf["time_step_max"],
    )
    moe = dataclasses.replace(
        cfg.moe, n_experts=conf["published"]["n_routed_experts"],
        held=conf["n_routed_experts"], first_held=conf["first_held"],
        top_k=conf["num_experts_per_tok"], d_ff=conf["moe_intermediate_size"],
        shared_d_ff=conf["moe_shared_expert_intermediate_size"] * conf["n_shared_experts"],
        routed_scale=conf["routed_scaling_factor"],
    )
    return cfg.replace(
        d_model=conf["hidden_size"], n_layers=conf["num_hidden_layers"],
        layer_pattern=conf["hybrid_override_pattern"], vocab_size=conf["vocab_size"],
        n_heads=conf["num_attention_heads"], n_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["head_dim"], d_ff=conf["intermediate_size"], mamba=mamba, moe=moe,
        param_dtype=a["param_dtype"], compute_dtype=a["compute_dtype"],
        opt_dtype=a["opt_dtype"], norm_eps=conf["norm_eps"],
    )


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def init(key, conf: dict) -> dict:
    """Initial weights from ``key`` (see the file's ``assumed.init``)."""
    d = dims(conf)
    D, di, H, N, G, K = d["D"], d["di"], d["H"], d["N"], d["G"], d["K"]
    U = d["L"] // len(d["pattern"])
    std = conf["assumed"]["initializer_range"]

    def normal(path, shape):
        return std * jax.random.normal(_key(key, path), (U,) + shape)

    def uniform(path, shape, bound):
        return jax.random.uniform(_key(key, path), (U,) + shape, jnp.float32, -bound, bound)

    def mamba(j):
        f = lambda name: f"L{j}.{name}"
        lo, hi = math.log(conf["time_step_min"]), math.log(conf["time_step_max"])
        dt = jnp.exp(jax.random.uniform(_key(key, f("dt")), (U, H)) * (hi - lo) + lo)
        dt = jnp.maximum(dt, conf["time_step_floor"])
        bound = K ** -0.5
        return {
            "wz": normal(f("wz"), (D, di)), "wx": normal(f("wx"), (D, di)),
            "wB": normal(f("wB"), (D, G, N)), "wC": normal(f("wC"), (D, G, N)),
            "wdt": normal(f("wdt"), (D, H)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "conv_wx": uniform(f("conv_wx"), (K, di), bound),
            "conv_bx": uniform(f("conv_bx"), (di,), bound),
            "conv_wB": uniform(f("conv_wB"), (K, G * N), bound),
            "conv_bB": uniform(f("conv_bB"), (G * N,), bound),
            "conv_wC": uniform(f("conv_wC"), (K, G * N), bound),
            "conv_bC": uniform(f("conv_bC"), (G * N,), bound),
            "A_log": jnp.broadcast_to(jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)), (U, H)),
            "D_skip": jnp.ones((U, H), jnp.float32),
            "norm": jnp.zeros((U, di), jnp.float32),
            "out": uniform(f("out"), (di, D), di ** -0.5)
            / math.sqrt(conf["published"]["num_hidden_layers"]),
        }

    def moe(j):
        f = lambda name: f"L{j}.{name}"
        return {
            "router": normal(f("router"), (D, d["E"])),
            "router_bias": jnp.zeros((U, d["E"]), jnp.float32),
            "wi": normal(f("wi"), (d["held"], D, d["F"])),
            "wo": normal(f("wo"), (d["held"], d["F"], D)),
            "shared": {"wi": normal(f("shared.wi"), (D, d["Fs"])),
                       "wo": normal(f("shared.wo"), (d["Fs"], D))},
        }

    def attn(j):
        f = lambda name: f"L{j}.{name}"
        return {
            "wq": normal(f("wq"), (D, d["Hq"], d["hd"])),
            "wk": normal(f("wk"), (D, d["KV"], d["hd"])),
            "wv": normal(f("wv"), (D, d["KV"], d["hd"])),
            "wo": normal(f("wo"), (d["Hq"], d["hd"], D)),
        }

    mixers = {"M": ("mamba", mamba), "E": ("ffn", moe), "*": ("attn", attn)}
    units = {}
    for j, kind in enumerate(d["pattern"]):
        name, make = mixers[kind]
        units[f"L{j}"] = {"ln": jnp.zeros((U, D), jnp.float32), name: make(j)}
    return {
        "embed": {"tok": std * jax.random.normal(_key(key, "tok"), (d["V"], D)),
                  "head": std * jax.random.normal(_key(key, "head"), (D, d["V"]))},
        "final_ln": jnp.zeros((D,), jnp.float32),
        "units": units,
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


def query_blocks(fn, S: int, *args):
    """``fn(t0, Q, *args)`` for query blocks [t0, t0 + Q) of a sequence of
    ``S``, each block recomputed in the backward, concatenated on axis 1."""
    Q = min(QUERY_BLOCK, S)
    one = jax.checkpoint(lambda t0: fn(t0, Q, *args))
    out = jax.lax.map(one, jnp.arange(0, S, Q))               # (S/Q, B, Q, ...)
    return jnp.moveaxis(out, 0, 1).reshape(out.shape[1], S, *out.shape[3:])


def ssd(x, dt, A, Bm, Cm, cast):
    """Quadratic SSD. x (B,S,H,P), dt (B,S,H), A (H,), Bm/Cm (B,S,G,N)."""
    Bsz, S, H, _ = x.shape
    G = Bm.shape[2]
    cum = jnp.cumsum(dt * A, axis=1)                          # (B,S,H)

    def block(t0, Q):
        t = t0 + jnp.arange(Q)
        diff = jax.lax.dynamic_slice_in_dim(cum, t0, Q, 1)[:, :, None, :] - cum[:, None]
        causal = (jnp.arange(S)[None, :] <= t[:, None])[None, :, :, None]
        decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))    # (B,t,s,H)
        Ct = jax.lax.dynamic_slice_in_dim(Cm, t0, Q, 1)
        cb = mm("btgn,bsgn->btsg", Ct, Bm, cast)              # (B,t,s,G)
        cb = jnp.repeat(cb, H // G, axis=-1)                  # head h reads group h // (H/G)
        w = cb * decay * dt[:, None, :, :]
        return mm("btsh,bshp->bthp", w, x, cast)

    return query_blocks(block, S)


def mamba(p, h, d, cast):
    Bsz, S, _ = h.shape
    H, P, N, G, di = d["H"], d["P"], d["N"], d["G"], d["di"]
    z = mm("bsd,di->bsi", h, p["wz"], cast)
    xc = mm("bsd,di->bsi", h, p["wx"], cast)
    Bv = mm("bsd,dgn->bsgn", h, p["wB"], cast).reshape(Bsz, S, G * N)
    Cv = mm("bsd,dgn->bsgn", h, p["wC"], cast).reshape(Bsz, S, G * N)
    dt = jax.nn.softplus(mm("bsd,dh->bsh", h, p["wdt"], cast) + p["dt_bias"])
    xc = jax.nn.silu(causal_conv(xc, p["conv_wx"], p["conv_bx"]))
    Bv = jax.nn.silu(causal_conv(Bv, p["conv_wB"], p["conv_bB"])).reshape(Bsz, S, G, N)
    Cv = jax.nn.silu(causal_conv(Cv, p["conv_wC"], p["conv_bC"])).reshape(Bsz, S, G, N)
    xh = xc.reshape(Bsz, S, H, P)
    y = ssd(xh, dt, -jnp.exp(p["A_log"]), Bv, Cv, cast)
    y = (y + xh * p["D_skip"][:, None]).reshape(Bsz, S, di) * jax.nn.silu(z)
    y = y.reshape(Bsz, S, G, di // G)                        # the norm is per group
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + d["eps"])
    y = y.reshape(Bsz, S, di) * (1.0 + p["norm"])
    return mm("bsi,id->bsd", y, p["out"], cast)


def attention(p, h, d, cast):
    Bsz, S, _ = h.shape
    KV, R, hd = d["KV"], d["Hq"] // d["KV"], d["hd"]
    q = mm("bsd,dhk->bshk", h, p["wq"], cast).reshape(Bsz, S, KV, R, hd)
    k = mm("bsd,dhk->bshk", h, p["wk"], cast)
    v = mm("bsd,dhk->bshk", h, p["wv"], cast)

    def block(t0, Q):
        qb = jax.lax.dynamic_slice_in_dim(q, t0, Q, 1)
        s = mm("btkrh,bskh->bkrts", qb, k, cast) / math.sqrt(hd)
        causal = jnp.arange(S)[None, :] <= (t0 + jnp.arange(Q))[:, None]
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return mm("bkrts,bskh->btkrh", a, v, cast)

    o = query_blocks(block, S).reshape(Bsz, S, d["Hq"], hd)
    return mm("bshk,hkd->bsd", o, p["wo"], cast)


def relu2_mlp(x, wi, wo, cast):
    return mm("tf,fd->td", jnp.square(jax.nn.relu(mm("td,df->tf", x, wi, cast))), wo, cast)


def moe(p, h, d, cast):
    """The held experts' part of the routed output, plus the shared expert."""
    shape = h.shape
    x = h.reshape(-1, shape[-1])
    scores = jax.nn.sigmoid(mm("td,de->te", x, p["router"], cast))
    _, top_e = jax.lax.top_k(scores + p["router_bias"], d["top_k"])
    top_w = jnp.take_along_axis(scores, top_e, axis=-1)
    top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20) * d["scale"]
    out = relu2_mlp(x, p["shared"]["wi"], p["shared"]["wo"], cast)
    for j in range(d["held"]):
        gate = jnp.sum(jnp.where(top_e == d["first"] + j, top_w, 0.0), axis=-1)
        out = out + gate[:, None] * relu2_mlp(x, p["wi"][j], p["wo"][j], cast)
    return out.reshape(shape)


MIXERS = {"M": ("mamba", mamba), "E": ("ffn", moe), "*": ("attn", attention)}


def loss(params, batch, conf, cast=lambda a: a):
    """Token-mean cross-entropy of one node's batch (B,S) in float32."""
    d = dims(conf)
    h = params["embed"]["tok"][batch["tokens"]]

    def unit(h, u):
        for j, kind in enumerate(d["pattern"]):
            name, mixer = MIXERS[kind]
            layer = lambda h, p: h + mixer(p[name], rmsnorm(h, p["ln"], d["eps"]), d, cast)
            h = jax.checkpoint(layer)(h, u[f"L{j}"])
        return h, None

    h, _ = jax.lax.scan(unit, h, params["units"])
    h = rmsnorm(h, params["final_ln"], d["eps"])
    logits = mm("bsd,dv->bsv", h, params["embed"]["head"], cast)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["labels"][..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)
