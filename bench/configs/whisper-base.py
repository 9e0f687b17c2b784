"""Plain reference of the encoder-decoder transformer that runs as
``whisper-base`` (Radford et al., arXiv:2212.04356, with the departures
listed in ``whisper-base.json``): its weights made from a seed, and its
training loss in float32 at the highest matmul precision, written from that
description with nothing taken from the program under test.

Attention is computed with its whole score matrix; the decoder's
self-attention is causal, the encoder's and the cross-attention are not.
Parameters are laid out as the program lays them out, layers stacked on a
leading axis. ``mm`` is where operands meet a matrix unit; the control
replaces its ``cast``.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
ROW_BLOCK = 16  # batch rows per reference pass, so that the logits fit


def dims(conf: dict) -> dict:
    d, h = conf["d_model"], conf["decoder_attention_heads"]
    return dict(D=d, H=h, hd=d // h, F=conf["decoder_ffn_dim"], V=conf["vocab_size"],
                Le=conf["encoder_layers"], Ld=conf["decoder_layers"],
                eps=conf["assumed"]["norm_epsilon"], theta=conf["assumed"]["rope_theta"])


def encoder_features(conf: dict):
    return conf["max_source_positions"], conf["d_model"]


def program_config(conf: dict, archs):
    """The program's ModelConfig, set from this file's numbers."""
    cfg = archs.get(conf["program_arch"])
    same = (conf["encoder_attention_heads"] == conf["decoder_attention_heads"]
            and conf["encoder_ffn_dim"] == conf["decoder_ffn_dim"])
    if not (cfg.enc_dec and same and conf["tie_word_embeddings"]):
        raise ValueError(f"{conf['program_arch']} is not a tied encoder-decoder")
    d, a = dims(conf), conf["assumed"]
    return cfg.replace(
        d_model=d["D"], n_layers=d["Ld"], n_enc_layers=d["Le"], n_heads=d["H"],
        n_kv_heads=d["H"], head_dim=d["hd"], d_ff=d["F"], vocab_size=d["V"],
        enc_frames=conf["max_source_positions"], rope_theta=a["rope_theta"],
        param_dtype=a["param_dtype"], compute_dtype=a["compute_dtype"],
        opt_dtype=a["opt_dtype"], norm_eps=a["norm_epsilon"],
    )


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def init(key, conf: dict) -> dict:
    """Initial weights from ``key`` (see the file's ``assumed.init``)."""
    d = dims(conf)
    D, H, hd, F, std = d["D"], d["H"], d["hd"], d["F"], conf["init_std"]

    def w(path, shape):
        k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
        return std * jax.random.normal(k, shape, jnp.float32)

    def attn(path, L):
        return {"wq": w(path + "wq", (L, D, H, hd)), "wk": w(path + "wk", (L, D, H, hd)),
                "wv": w(path + "wv", (L, D, H, hd)), "wo": w(path + "wo", (L, H, hd, D))}

    def ffn(path, L):
        return {"wi": w(path + "wi", (L, D, F)), "wg": w(path + "wg", (L, D, F)),
                "wo": w(path + "wo", (L, F, D))}

    zeros = lambda *s: jnp.zeros(s, jnp.float32)
    Le, Ld = d["Le"], d["Ld"]
    return {
        "embed": {"tok": w("tok", (d["V"], D))},
        "final_ln": zeros(D),
        "units": {"L0": {
            "ln": zeros(Ld, D), "attn": attn("dec.self.", Ld),
            "cross_ln": zeros(Ld, D), "cross": attn("dec.cross.", Ld),
            "ln2": zeros(Ld, D), "ffn": ffn("dec.ffn.", Ld),
        }},
        "encoder": {
            "blocks": {"ln": zeros(Le, D), "attn": attn("enc.self.", Le),
                       "ln2": zeros(Le, D), "ffn": ffn("enc.ffn.", Le)},
            "final_ln": zeros(D),
        },
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


def rope(x, theta):
    """Rotary positions 0..S-1 on (B,S,H,hd), halves rotated as pairs."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = theta ** -(jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(p, xq, xkv, d, cast, causal, positions):
    q = mm("bsd,dhk->bshk", xq, p["wq"], cast)
    k = mm("bsd,dhk->bshk", xkv, p["wk"], cast)
    v = mm("bsd,dhk->bshk", xkv, p["wv"], cast)
    if positions:
        q, k = rope(q, d["theta"]), rope(k, d["theta"])
    s = mm("bqhk,bshk->bhqs", q, k, cast) * d["hd"] ** -0.5
    if causal:
        n = s.shape[-1]
        s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
    o = mm("bhqs,bshk->bqhk", jax.nn.softmax(s, axis=-1), v, cast)
    return mm("bqhk,hkd->bqd", o, p["wo"], cast)


def ffn(p, x, cast):
    gate = jax.nn.gelu(mm("bsd,df->bsf", x, p["wg"], cast), approximate=True)
    return mm("bsf,fd->bsd", gate * mm("bsd,df->bsf", x, p["wi"], cast), p["wo"], cast)


def loss(params, batch, conf, cast=lambda a: a):
    """Token-mean cross-entropy of a batch (B,S) of decoder tokens given
    encoder features (B,F,D), in float32."""
    d = dims(conf)

    @jax.checkpoint
    def enc_layer(h, p):
        h = h + attention(p["attn"], rmsnorm(h, p["ln"], d["eps"]),
                          rmsnorm(h, p["ln"], d["eps"]), d, cast, False, True)
        return h + ffn(p["ffn"], rmsnorm(h, p["ln2"], d["eps"]), cast), None

    enc, _ = jax.lax.scan(enc_layer, batch["enc_embeds"], params["encoder"]["blocks"])
    enc = rmsnorm(enc, params["encoder"]["final_ln"], d["eps"])

    @jax.checkpoint
    def dec_layer(h, p):
        x = rmsnorm(h, p["ln"], d["eps"])
        h = h + attention(p["attn"], x, x, d, cast, True, True)
        h = h + attention(p["cross"], rmsnorm(h, p["cross_ln"], d["eps"]), enc,
                          d, cast, False, False)
        return h + ffn(p["ffn"], rmsnorm(h, p["ln2"], d["eps"]), cast), None

    h = params["embed"]["tok"][batch["tokens"]]
    h, _ = jax.lax.scan(dec_layer, h, params["units"]["L0"])
    h = rmsnorm(h, params["final_ln"], d["eps"])
    logits = mm("bsd,vd->bsv", h, params["embed"]["tok"], cast)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["labels"][..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)
