"""The LM decoder stack: composable blocks covering every assigned family.

A config is compiled (in Python) to a list of :class:`LayerDesc` per *scan
unit*; units are homogeneous, so the whole depth is a single ``lax.scan``
over stacked params (O(1) HLO in depth):

- dense (granite/qwen2/qwen2-vl):  unit = [attn+mlp],        L units
- gemma2:                          unit = [attn(local)+mlp,
                                           attn(global)+mlp], L/2 units
- moe (qwen3-moe/kimi-k2):         unit = [attn+moe],         L units
- ssm (mamba2):                    unit = [mamba],            L units
- hybrid (jamba):                  unit = [attn+mlp, (mamba+moe, mamba+mlp)
                                           alternating x7],   L/8 units
- layer pattern (nemotron-h):      unit = one single-mixer layer per
                                   pattern letter (M mamba, E moe,
                                   * attn), L/len(pattern) units
- whisper decoder:                 unit = [attn+cross+mlp],   L units

A layer is ``h + mixer(norm(h))`` and then, if it has an FFN,
``h + ffn(norm2(h))``; a pattern's MoE layer has no token mixer. Each
mixer runs under a named scope (``mamba``, ``attention``, ``moe``), so a
profile of a training step attributes time to the kinds of layer.

Caches: per attention layer a KV ring buffer (length = window for local
layers — a sliding-window cache — else the max sequence length), per mamba
layer the (ssm, conv) recurrent state, per cross-attn layer the frozen
encoder KV. Decode scans units with the stacked cache as scan xs/ys.
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.launch.sharding import shard_activation
from repro.models import mamba2 as mamba_lib
from repro.models import moe as moe_lib
from repro.models.attention import (
    AttnSpec,
    flash_attention_decode,
    flash_attention_train,
    kernel_applies,
)
from repro.models.config import ModelConfig
from repro.models.layers import (
    apply_mrope,
    apply_rope,
    dtype_of,
    embed_tokens,
    init_embedding,
    init_mlp,
    init_rmsnorm,
    lm_logits,
    mlp_apply,
    rmsnorm,
    stack_params,
    truncated_normal,
)

Params = Dict[str, Any]


@dataclass(frozen=True)
class LayerDesc:
    mixer: Optional[str]        # "attn" | "mamba" | None (an FFN-only layer)
    local: bool = False         # sliding-window attention
    ffn: Optional[str] = None   # "dense" | "moe" | None
    cross: bool = False         # cross-attention (whisper decoder)


def scan_unit(cfg: ModelConfig) -> List[LayerDesc]:
    """The per-unit layer pattern for this config (see module docstring)."""
    if cfg.layer_pattern is not None:
        kinds = {
            "M": LayerDesc("mamba"),
            "*": LayerDesc("attn", local=cfg.force_local),
            "E": LayerDesc(None, ffn="moe"),
        }
        return [kinds[c] for c in cfg.layer_pattern]
    if cfg.family == "ssm":
        return [LayerDesc("mamba", ffn=None if cfg.no_ffn else "dense")]
    if cfg.family == "hybrid":
        period = cfg.attn_every
        descs = []
        for j in range(period):
            mixer = "attn" if j == 0 else "mamba"
            ffn = "moe" if cfg.ffn_is_moe(j) else "dense"
            descs.append(
                LayerDesc(mixer, local=cfg.layer_is_local(j) or cfg.force_local, ffn=ffn)
            )
        return descs
    if cfg.local_global_alternate:
        return [
            LayerDesc("attn", local=True, ffn="moe" if cfg.ffn_is_moe(0) else "dense"),
            LayerDesc("attn", local=False, ffn="moe" if cfg.ffn_is_moe(1) else "dense"),
        ]
    ffn = "moe" if (cfg.moe is not None and cfg.moe.every == 1) else "dense"
    return [LayerDesc("attn", local=cfg.force_local, ffn=ffn, cross=cfg.enc_dec)]


def n_units(cfg: ModelConfig) -> int:
    return cfg.n_layers // len(scan_unit(cfg))


# ---------------------------------------------------------------------------
# attention sub-layer
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig) -> Tuple[Params, Dict]:
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = dtype_of(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    std = D ** -0.5
    p = {
        "wq": truncated_normal(ks[0], (D, H, hd), std, dt),
        "wk": truncated_normal(ks[1], (D, KV, hd), std, dt),
        "wv": truncated_normal(ks[2], (D, KV, hd), std, dt),
        "wo": truncated_normal(ks[3], (H, hd, D), (H * hd) ** -0.5, dt),
    }
    s = {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H, hd), dtype=dt)
        p["bk"] = jnp.zeros((KV, hd), dtype=dt)
        p["bv"] = jnp.zeros((KV, hd), dtype=dt)
        s["bq"] = ("heads", "head_dim")
        s["bk"] = ("kv_heads", "head_dim")
        s["bv"] = ("kv_heads", "head_dim")
    return p, s


def _qkv(p: Params, x: jax.Array, cfg: ModelConfig):
    cdt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(cdt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(cdt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(cdt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(cdt)[None, None]
        k = k + p["bk"].astype(cdt)[None, None]
        v = v + p["bv"].astype(cdt)[None, None]
    q = shard_activation(q, ("batch", "seq", "heads", None))
    k = shard_activation(k, ("batch", "seq", "kv_heads", None))
    v = shard_activation(v, ("batch", "seq", "kv_heads", None))
    return q, k, v


def _rope_qk(q, k, positions, cfg: ModelConfig):
    if cfg.rope_theta is None:
        return q, k
    if cfg.mrope_sections is not None:
        q = apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k


def _attn_spec(cfg: ModelConfig, desc: LayerDesc, causal: bool = True) -> AttnSpec:
    return AttnSpec(
        causal=causal,
        window=cfg.sliding_window if desc.local else None,
        softcap=cfg.attn_softcap,
        block_q=cfg.attn_block_q,
        block_k=cfg.attn_block_k,
    )


def attention_kernel_layers(cfg: ModelConfig, batch: int, seq: int) -> int:
    """How many attention calls of one training step over ``batch`` x ``seq``
    tokens run the fused Pallas kernel, by the predicate that dispatches
    them (:func:`repro.models.attention.kernel_applies`): self-attention and
    cross-attention of each unit, and the encoder's layers."""
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (batch, seq, H, hd)
    full = _attn_spec(cfg, LayerDesc("attn"), causal=False)
    n = 0
    for d in scan_unit(cfg):
        if d.mixer == "attn":
            n += kernel_applies(q, (batch, seq, KV, hd), _attn_spec(cfg, d))
            if d.cross:
                n += kernel_applies(q, (batch, cfg.enc_frames, KV, hd), full)
    n *= n_units(cfg)
    if cfg.enc_dec:
        frames = (batch, cfg.enc_frames)
        n += cfg.n_enc_layers * kernel_applies((*frames, H, hd), (*frames, KV, hd), full)
    return n


def attn_train(
    p: Params, x: jax.Array, positions, cfg: ModelConfig, desc: LayerDesc,
    causal: bool = True,
) -> jax.Array:
    q, k, v = _qkv(p, x, cfg)
    q, k = _rope_qk(q, k, positions, cfg)
    out = flash_attention_train(q, k, v, _attn_spec(cfg, desc, causal))
    out = shard_activation(out, ("batch", "seq", "heads", None))
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(out.dtype))


def cross_attn_train(p: Params, x: jax.Array, enc_kv, cfg: ModelConfig) -> jax.Array:
    """Cross-attention against precomputed encoder K/V (no rope, no mask)."""
    cdt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(cdt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(cdt)[None, None]
    k, v = enc_kv
    spec = AttnSpec(causal=False, window=None, softcap=cfg.attn_softcap,
                    block_q=cfg.attn_block_q, block_k=cfg.attn_block_k)
    out = flash_attention_train(q, k, v, spec)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(out.dtype))


def enc_kv_for_cross(p: Params, enc_out: jax.Array, cfg: ModelConfig):
    cdt = enc_out.dtype
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"].astype(cdt))
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"].astype(cdt))
    if cfg.qkv_bias:
        k = k + p["bk"].astype(cdt)[None, None]
        v = v + p["bv"].astype(cdt)[None, None]
    return k, v


# ---------------------------------------------------------------------------
# unit (scan body) param init
# ---------------------------------------------------------------------------

def init_unit(key, cfg: ModelConfig) -> Tuple[Params, Dict]:
    descs = scan_unit(cfg)
    p, s = {}, {}
    for j, d in enumerate(descs):
        kj = jax.random.fold_in(key, j)
        name = f"L{j}"
        lp, ls = {}, {}
        lp["ln"], ls["ln"] = init_rmsnorm(cfg.d_model, dtype_of(cfg.param_dtype))
        if d.mixer == "attn":
            lp["attn"], ls["attn"] = init_attention(jax.random.fold_in(kj, 0), cfg)
        elif d.mixer == "mamba":
            lp["mamba"], ls["mamba"] = mamba_lib.init_mamba(
                jax.random.fold_in(kj, 1), cfg
            )
        if d.cross:
            lp["cross_ln"], ls["cross_ln"] = init_rmsnorm(
                cfg.d_model, dtype_of(cfg.param_dtype)
            )
            lp["cross"], ls["cross"] = init_attention(jax.random.fold_in(kj, 2), cfg)
        if d.ffn is not None:
            if d.mixer is not None:
                lp["ln2"], ls["ln2"] = init_rmsnorm(
                    cfg.d_model, dtype_of(cfg.param_dtype)
                )
            if d.ffn == "moe":
                lp["ffn"], ls["ffn"] = moe_lib.init_moe(jax.random.fold_in(kj, 3), cfg)
            else:
                lp["ffn"], ls["ffn"] = init_mlp(
                    jax.random.fold_in(kj, 3), cfg, cfg.d_ff
                )
        p[name], s[name] = lp, ls
    return p, s


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

class KVCache(NamedTuple):
    k: jax.Array   # (B, Sc, KV, hd)
    v: jax.Array


def layer_cache_len(cfg: ModelConfig, desc: LayerDesc, max_len: int) -> int:
    if desc.local and cfg.sliding_window is not None:
        return min(max_len, cfg.sliding_window)
    return max_len


def init_unit_cache(cfg: ModelConfig, batch: int, max_len: int) -> Dict:
    """Zero cache for ONE unit (to be stacked/broadcast over units)."""
    descs = scan_unit(cfg)
    cdt = dtype_of(cfg.compute_dtype)
    KV, hd = cfg.n_kv_heads, cfg.head_dim
    cache: Dict[str, Any] = {}
    for j, d in enumerate(descs):
        if d.mixer == "attn":
            L = layer_cache_len(cfg, d, max_len)
            cache[f"kv{j}"] = KVCache(
                k=jnp.zeros((batch, L, KV, hd), cdt),
                v=jnp.zeros((batch, L, KV, hd), cdt),
            )
            if d.cross:
                cache[f"cross{j}"] = KVCache(
                    k=jnp.zeros((batch, cfg.enc_frames, KV, hd), cdt),
                    v=jnp.zeros((batch, cfg.enc_frames, KV, hd), cdt),
                )
        elif d.mixer == "mamba":
            mb = cfg.mamba
            Hm = mb.n_heads(cfg.d_model)
            conv_dim = mb.d_inner(cfg.d_model) + 2 * mb.n_groups * mb.d_state
            cache[f"mamba{j}"] = mamba_lib.MambaCache(
                ssm=jnp.zeros((batch, Hm, mb.head_dim, mb.d_state), jnp.float32),
                conv=jnp.zeros((batch, mb.d_conv - 1, conv_dim), cdt),
            )
    return cache


def cache_logical_specs(cfg: ModelConfig) -> Dict:
    descs = scan_unit(cfg)
    spec: Dict[str, Any] = {}
    for j, d in enumerate(descs):
        if d.mixer == "attn":
            spec[f"kv{j}"] = KVCache(
                k=("layers", "batch", "cache_seq", "kv_heads", None),
                v=("layers", "batch", "cache_seq", "kv_heads", None),
            )
            if d.cross:
                spec[f"cross{j}"] = KVCache(
                    k=("layers", "batch", "frames", "kv_heads", None),
                    v=("layers", "batch", "frames", "kv_heads", None),
                )
        elif d.mixer == "mamba":
            spec[f"mamba{j}"] = mamba_lib.MambaCache(
                ssm=("layers", "batch", "mamba_heads", None, None),
                conv=("layers", "batch", None, None),  # tiny: keep whole
            )
    return spec


# ---------------------------------------------------------------------------
# model init
# ---------------------------------------------------------------------------

def init_params(key, cfg: ModelConfig) -> Tuple[Params, Dict]:
    k_embed, k_units, k_final, k_enc = jax.random.split(key, 4)
    params: Params = {}
    specs: Dict = {}
    params["embed"], specs["embed"] = init_embedding(k_embed, cfg)
    params["units"], specs["units"] = stack_params(
        k_units, n_units(cfg), lambda k: init_unit(k, cfg)
    )
    params["final_ln"], specs["final_ln"] = init_rmsnorm(
        cfg.d_model, dtype_of(cfg.param_dtype)
    )
    if cfg.enc_dec:
        params["encoder"], specs["encoder"] = init_encoder(k_enc, cfg)
    return params, specs


def init_encoder(key, cfg: ModelConfig) -> Tuple[Params, Dict]:
    def init_one(k):
        p, s = {}, {}
        p["ln"], s["ln"] = init_rmsnorm(cfg.d_model, dtype_of(cfg.param_dtype))
        p["attn"], s["attn"] = init_attention(jax.random.fold_in(k, 0), cfg)
        p["ln2"], s["ln2"] = init_rmsnorm(cfg.d_model, dtype_of(cfg.param_dtype))
        p["ffn"], s["ffn"] = init_mlp(jax.random.fold_in(k, 1), cfg, cfg.d_ff)
        return p, s

    p, s = {}, {}
    p["blocks"], s["blocks"] = stack_params(key, cfg.n_enc_layers, init_one)
    p["final_ln"], s["final_ln"] = init_rmsnorm(
        cfg.d_model, dtype_of(cfg.param_dtype)
    )
    return p, s


def remat_layer(body: Callable, cfg: ModelConfig) -> Callable:
    """A layer scan's body under ``cfg.remat``. With ``full`` the backward
    recomputes the layer's forward from its input and the values saved by
    name (``mamba2.SAVED``: a Mamba-2 mixer's in-projections and SSD
    output); a layer that names nothing saves only its input."""
    if cfg.remat != "full":
        return body
    return jax.checkpoint(
        body, policy=jax.checkpoint_policies.save_only_these_names(mamba_lib.SAVED)
    )


# ---------------------------------------------------------------------------
# encoder forward (whisper)
# ---------------------------------------------------------------------------

def encoder_forward(params: Params, enc_embeds: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Bidirectional encoder over stub frame embeddings (B, F, D)."""
    h = enc_embeds.astype(dtype_of(cfg.compute_dtype))
    B, F, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(F)[None], (B, F))

    def body(h, p):
        hn = rmsnorm(h, p["ln"], cfg.norm_eps)
        q, k, v = _qkv(p["attn"], hn, cfg)
        q, k = _rope_qk(q, k, positions, cfg)
        spec = AttnSpec(causal=False, softcap=cfg.attn_softcap,
                        block_q=cfg.attn_block_q, block_k=cfg.attn_block_k)
        a = flash_attention_train(q, k, v, spec)
        h = h + jnp.einsum("bshk,hkd->bsd", a, p["attn"]["wo"].astype(a.dtype))
        hn = rmsnorm(h, p["ln2"], cfg.norm_eps)
        h = h + mlp_apply(p["ffn"], hn, cfg)
        return h, None

    h, _ = jax.lax.scan(remat_layer(body, cfg), h, params["blocks"])
    return rmsnorm(h, params["final_ln"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# decoder forward: train / prefill
# ---------------------------------------------------------------------------

def _unit_forward(
    h: jax.Array,
    unit_p: Params,
    positions,
    cfg: ModelConfig,
    enc_out: Optional[jax.Array],
    collect_cache: bool,
    max_len: int,
    layer_remat: Callable = lambda fn: fn,
):
    """Apply one unit, each layer through ``layer_remat``. Returns (h,
    aux_losses, cache_entries)."""
    descs = scan_unit(cfg)
    aux = {"moe_aux": jnp.zeros((), jnp.float32), "moe_zloss": jnp.zeros((), jnp.float32)}
    cache_out: Dict[str, Any] = {}
    for j, d in enumerate(descs):
        layer = functools.partial(
            _layer_forward, d=d, j=j, positions=positions, cfg=cfg, enc_out=enc_out,
            collect_cache=collect_cache, max_len=max_len,
        )
        h, a, entries = layer_remat(layer)(h, unit_p[f"L{j}"])
        if a is not None:
            aux = {k_: aux[k_] + a[k_] for k_ in aux}
        cache_out.update(entries)
    return h, aux, cache_out


def _layer_forward(h, p, *, d: LayerDesc, j: int, positions, cfg: ModelConfig,
                   enc_out, collect_cache: bool, max_len: int):
    """One layer. Returns (h, the MoE's aux losses or None, cache entries)."""
    aux = None
    cache_out: Dict[str, Any] = {}
    if d.mixer is not None:
        hn = rmsnorm(h, p["ln"], cfg.norm_eps)
    if d.mixer == "attn":
        with jax.named_scope("attention"):
            q, k, v = _qkv(p["attn"], hn, cfg)
            q, k = _rope_qk(q, k, positions, cfg)
            out = flash_attention_train(q, k, v, _attn_spec(cfg, d))
            out = shard_activation(out, ("batch", "seq", "heads", None))
            h = h + jnp.einsum("bshk,hkd->bsd", out, p["attn"]["wo"].astype(out.dtype))
        if collect_cache:
            cache_out[f"kv{j}"] = _prefill_kv_cache(k, v, cfg, d, max_len)
        if d.cross:
            assert enc_out is not None
            hc = rmsnorm(h, p["cross_ln"], cfg.norm_eps)
            enc_kv = enc_kv_for_cross(p["cross"], enc_out, cfg)
            h = h + cross_attn_train(p["cross"], hc, enc_kv, cfg)
            if collect_cache:
                cache_out[f"cross{j}"] = KVCache(k=enc_kv[0], v=enc_kv[1])
    elif d.mixer == "mamba":
        with jax.named_scope("mamba"):
            if collect_cache:
                out, mcache = mamba_lib.mamba_prefill(p["mamba"], hn, cfg)
                cache_out[f"mamba{j}"] = mcache
            else:
                out = mamba_lib.mamba_forward(p["mamba"], hn, cfg)
            h = h + out
    if d.ffn is not None:
        hn = rmsnorm(h, p["ln2" if d.mixer is not None else "ln"], cfg.norm_eps)
        if d.ffn == "moe":
            with jax.named_scope("moe"):
                out, aux = moe_lib.moe_apply(p["ffn"], hn, cfg)
        else:
            out = mlp_apply(p["ffn"], hn, cfg)
        h = h + out
    h = shard_activation(h, ("batch", "seq", None))
    return h, aux, cache_out


def _prefill_kv_cache(k, v, cfg: ModelConfig, desc: LayerDesc, max_len: int) -> KVCache:
    """Arrange prefill K/V into the decode cache layout (ring for local)."""
    B, S = k.shape[:2]
    L = layer_cache_len(cfg, desc, max_len)
    if L >= max_len and S <= L:
        pad = L - S
        kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return KVCache(k=kc, v=vc)
    # ring: slot t%L holds the last position congruent to t
    W = L
    tail_positions = (S - W + jnp.arange(W)) % W if S >= W else None
    if S >= W:
        k_tail, v_tail = k[:, S - W :], v[:, S - W :]
        kc = jnp.zeros_like(k_tail).at[:, tail_positions].set(k_tail)
        vc = jnp.zeros_like(v_tail).at[:, tail_positions].set(v_tail)
        return KVCache(k=kc, v=vc)
    pad = W - S
    return KVCache(
        k=jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))),
        v=jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))),
    )


def forward_train(
    params: Params,
    tokens: jax.Array,
    cfg: ModelConfig,
    positions: Optional[jax.Array] = None,
    enc_embeds: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Full decoder forward. Returns (hidden (B,S,D), aux losses)."""
    B, S = tokens.shape
    h = embed_tokens(params["embed"], tokens, cfg)
    h = shard_activation(h, ("batch", "seq", None))
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        if cfg.mrope_sections is not None:
            positions = jnp.broadcast_to(positions[..., None], (B, S, 3))
    enc_out = None
    if cfg.enc_dec:
        assert enc_embeds is not None
        enc_out = encoder_forward(params["encoder"], enc_embeds, cfg)

    # a pattern's unit is a whole period of layers: each is its own
    # checkpoint, so the backward holds one layer's recompute at a time
    per_layer = cfg.layer_pattern is not None
    layer_remat = functools.partial(remat_layer, cfg=cfg) if per_layer else (lambda fn: fn)

    def body(carry, unit_p):
        h, aux = carry
        h, a, _ = _unit_forward(
            h, unit_p, positions, cfg, enc_out, False, S, layer_remat=layer_remat
        )
        aux = {k_: aux[k_] + a[k_] for k_ in aux}
        return (h, aux), None

    fn = body if per_layer else remat_layer(body, cfg)
    aux0 = {
        "moe_aux": jnp.zeros((), jnp.float32),
        "moe_zloss": jnp.zeros((), jnp.float32),
    }
    (h, aux), _ = jax.lax.scan(fn, (h, aux0), params["units"])
    h = rmsnorm(h, params["final_ln"], cfg.norm_eps)
    return h, aux


def loss_fn(
    params: Params,
    batch: Dict[str, jax.Array],
    cfg: ModelConfig,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Token-mean cross-entropy with seq-chunked vocab projection."""
    tokens = batch["tokens"]
    labels = batch["labels"]
    h, aux = forward_train(
        params, tokens, cfg,
        positions=batch.get("positions"),
        enc_embeds=batch.get("enc_embeds"),
    )
    B, S, D = h.shape
    chunk = min(cfg.loss_chunk, S)
    nch = S // chunk
    h_c = h.reshape(B, nch, chunk, D).transpose(1, 0, 2, 3)
    y_c = labels.reshape(B, nch, chunk).transpose(1, 0, 2)

    def chunk_loss(carry, xs):
        hc, yc = xs
        logits = lm_logits(params["embed"], hc, cfg)        # (B,chunk,V) fp32
        logits = shard_activation(logits, ("batch", "seq", "vocab"))
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yc[..., None], axis=-1)[..., 0]
        return carry + jnp.sum(lse - gold), None

    total, _ = jax.lax.scan(
        jax.checkpoint(chunk_loss), jnp.zeros((), jnp.float32), (h_c, y_c)
    )
    loss = total / (B * S)
    metrics = {"ce_loss": loss, **aux}
    total_loss = loss + aux["moe_aux"] + aux["moe_zloss"]
    return total_loss, metrics


# ---------------------------------------------------------------------------
# prefill / decode
# ---------------------------------------------------------------------------

def prefill(
    params: Params,
    tokens: jax.Array,
    cfg: ModelConfig,
    max_len: int,
    positions: Optional[jax.Array] = None,
    enc_embeds: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict]:
    """Run the prompt, build the decode cache. Returns (last-token logits,
    cache). ``max_len`` sizes the cache."""
    B, S = tokens.shape
    h = embed_tokens(params["embed"], tokens, cfg)
    h = shard_activation(h, ("batch", "seq", None))
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        if cfg.mrope_sections is not None:
            positions = jnp.broadcast_to(positions[..., None], (B, S, 3))
    enc_out = None
    if cfg.enc_dec:
        assert enc_embeds is not None
        enc_out = encoder_forward(params["encoder"], enc_embeds, cfg)

    def body(h, unit_p):
        h, _, cache_entries = _unit_forward(
            h, unit_p, positions, cfg, enc_out, True, max_len
        )
        return h, cache_entries

    h, unit_caches = jax.lax.scan(body, h, params["units"])
    h = rmsnorm(h, params["final_ln"], cfg.norm_eps)
    logits = lm_logits(params["embed"], h[:, -1:], cfg)
    cache = {"pos": jnp.full((), S, jnp.int32), "units": unit_caches}
    return logits, cache


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Dict:
    one = init_unit_cache(cfg, batch, max_len)
    U = n_units(cfg)
    units = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (U,) + x.shape), one)
    return {"pos": jnp.zeros((), jnp.int32), "units": units}


def decode_step(
    params: Params,
    cache: Dict,
    token: jax.Array,                 # (B, 1) int32
    cfg: ModelConfig,
    positions: Optional[jax.Array] = None,   # (B, 1[,3]) for M-RoPE
) -> Tuple[jax.Array, Dict]:
    """One serving step: next-token logits + updated cache."""
    B = token.shape[0]
    pos = cache["pos"]
    h = embed_tokens(params["embed"], token, cfg)
    if positions is None:
        positions = jnp.broadcast_to(pos[None, None], (B, 1))
        if cfg.mrope_sections is not None:
            positions = jnp.broadcast_to(positions[..., None], (B, 1, 3))
    descs = scan_unit(cfg)

    def body(h, xs):
        unit_p, unit_c = xs
        new_c = dict(unit_c)
        for j, d in enumerate(descs):
            p = unit_p[f"L{j}"]
            if d.mixer is not None:
                hn = rmsnorm(h, p["ln"], cfg.norm_eps)
            if d.mixer == "attn":
                q, k, v = _qkv(p["attn"], hn, cfg)
                q, k = _rope_qk(q, k, positions, cfg)
                kv: KVCache = unit_c[f"kv{j}"]
                L = kv.k.shape[1]
                slot = pos % L
                kc = jax.lax.dynamic_update_slice_in_dim(kv.k, k, slot, axis=1)
                vc = jax.lax.dynamic_update_slice_in_dim(kv.v, v, slot, axis=1)
                # pin the cache layout: left unconstrained, GSPMD may flip
                # the (batch-sharded) cache to kv-head sharding mid-program
                # and gather the WHOLE cache back (measured: 2 x 86 GB/step
                # on qwen2-72b decode_32k)
                kc = shard_activation(kc, ("batch", "cache_seq", "kv_heads", None))
                vc = shard_activation(vc, ("batch", "cache_seq", "kv_heads", None))
                new_c[f"kv{j}"] = KVCache(k=kc, v=vc)
                kv_len = jnp.minimum(pos + 1, L)
                spec = AttnSpec(causal=False, window=None, softcap=cfg.attn_softcap,
                                block_q=cfg.attn_block_q, block_k=cfg.attn_block_k)
                out = flash_attention_decode(q, kc, vc, spec, q_offset=pos,
                                             kv_len=kv_len)
                h = h + jnp.einsum(
                    "bshk,hkd->bsd", out, p["attn"]["wo"].astype(out.dtype)
                )
                if d.cross:
                    hc = rmsnorm(h, p["cross_ln"], cfg.norm_eps)
                    ckv: KVCache = unit_c[f"cross{j}"]
                    cdt = hc.dtype
                    q2 = jnp.einsum("bsd,dhk->bshk", hc, p["cross"]["wq"].astype(cdt))
                    if cfg.qkv_bias:
                        q2 = q2 + p["cross"]["bq"].astype(cdt)[None, None]
                    spec2 = AttnSpec(causal=False, softcap=cfg.attn_softcap,
                                     block_q=cfg.attn_block_q, block_k=cfg.attn_block_k)
                    out2 = flash_attention_decode(q2, ckv.k, ckv.v, spec2, q_offset=0)
                    h = h + jnp.einsum(
                        "bshk,hkd->bsd", out2, p["cross"]["wo"].astype(out2.dtype)
                    )
            elif d.mixer == "mamba":
                mc: mamba_lib.MambaCache = unit_c[f"mamba{j}"]
                out, new_mc = mamba_lib.mamba_decode_step(p["mamba"], hn, mc, cfg)
                new_c[f"mamba{j}"] = new_mc
                h = h + out
            if d.ffn is not None:
                hn = rmsnorm(h, p["ln2" if d.mixer is not None else "ln"], cfg.norm_eps)
                if d.ffn == "moe":
                    out, _ = moe_lib.moe_apply(p["ffn"], hn, cfg)
                else:
                    out = mlp_apply(p["ffn"], hn, cfg)
                h = h + out
        return h, new_c

    h, new_units = jax.lax.scan(body, h, (params["units"], cache["units"]))
    h = rmsnorm(h, params["final_ln"], cfg.norm_eps)
    logits = lm_logits(params["embed"], h, cfg)
    return logits, {"pos": pos + 1, "units": new_units}
