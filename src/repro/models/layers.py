"""Base layers: params-as-pytrees with logical sharding axes, norms,
embeddings, RoPE (+ M-RoPE), gated and plain MLPs.

Convention: every ``init_*`` returns ``(params, specs)`` where ``specs``
mirrors the params pytree and holds a tuple of *logical axis names* per
array. The launch layer maps logical axes to mesh axes (TP/EP/FSDP) —
models never mention the mesh.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig

Params = Dict[str, Any]
Specs = Dict[str, Any]


def dtype_of(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "int8": jnp.int8}[name]


def truncated_normal(key, shape, stddev, dtype):
    return (stddev * jax.random.truncated_normal(key, -2.0, 2.0, shape)).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int, dtype) -> Tuple[jax.Array, Tuple]:
    return jnp.zeros((d,), dtype=dtype), ("embed",)


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    # "zero-centered" scale (gemma/llama style: weight stored as offset from 1)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dt)


# ---------------------------------------------------------------------------
# embedding
# ---------------------------------------------------------------------------

def init_embedding(key, cfg: ModelConfig) -> Tuple[Params, Specs]:
    dt = dtype_of(cfg.param_dtype)
    p = {"tok": truncated_normal(key, (cfg.vocab_size, cfg.d_model), 1.0, dt)}
    s = {"tok": ("vocab", "embed")}
    if not cfg.tie_embeddings:
        k2 = jax.random.fold_in(key, 1)
        p["head"] = truncated_normal(
            k2, (cfg.d_model, cfg.vocab_size), cfg.d_model ** -0.5, dt
        )
        s["head"] = ("embed", "vocab")
    return p, s


def embed_tokens(params: Params, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    h = params["tok"].astype(dtype_of(cfg.compute_dtype))[tokens]
    if cfg.embed_scale:
        h = h * jnp.asarray(math.sqrt(cfg.d_model), h.dtype)
    return h


def lm_logits(params: Params, h: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Final projection; applies gemma2's final logit softcap when set."""
    if cfg.tie_embeddings:
        w = params["tok"].astype(h.dtype)  # (V, D)
        logits = jnp.einsum("...d,vd->...v", h, w, preferred_element_type=jnp.float32)
    else:
        w = params["head"].astype(h.dtype)  # (D, V)
        logits = jnp.einsum("...d,dv->...v", h, w, preferred_element_type=jnp.float32)
    if cfg.final_softcap is not None:
        c = cfg.final_softcap
        logits = c * jnp.tanh(logits / c)
    return logits


# ---------------------------------------------------------------------------
# RoPE and M-RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponents)  # (hd/2,)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, N, hd); positions: (B, S) int32."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                      # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, hd/2)
    sin, cos = jnp.sin(ang)[:, :, None, :], jnp.cos(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(
    x: jax.Array,
    positions3: jax.Array,
    theta: float,
    sections: Tuple[int, int, int],
) -> jax.Array:
    """Qwen2-VL multimodal RoPE: rotary dims split into (temporal, height,
    width) sections, each rotated by its own position stream.

    x: (B, S, N, hd); positions3: (B, S, 3) int32. ``sections`` counts
    frequency PAIRS per component and must sum to hd/2.
    """
    hd = x.shape[-1]
    assert sum(sections) == hd // 2, (sections, hd)
    freqs = rope_freqs(hd, theta)                      # (hd/2,)
    # pick per-frequency position component
    comp = jnp.concatenate(
        [jnp.full((s,), i, dtype=jnp.int32) for i, s in enumerate(sections)]
    )                                                  # (hd/2,)
    pos = jnp.take_along_axis(
        positions3.astype(jnp.float32),                # (B, S, 3)
        jnp.broadcast_to(comp[None, None, :], positions3.shape[:2] + comp.shape),
        axis=-1,
    )                                                  # (B, S, hd/2)
    ang = pos * freqs
    sin, cos = jnp.sin(ang)[:, :, None, :], jnp.cos(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# gated MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig, d_ff: int) -> Tuple[Params, Specs]:
    dt = dtype_of(cfg.param_dtype)
    D = cfg.d_model
    k1, k2, k3 = jax.random.split(key, 3)
    std_in, std_out = D ** -0.5, d_ff ** -0.5
    p = {
        "wi": truncated_normal(k1, (D, d_ff), std_in, dt),
        "wo": truncated_normal(k3, (d_ff, D), std_out, dt),
    }
    s = {"wi": ("embed", "mlp"), "wo": ("mlp", "embed")}
    if cfg.gated_mlp:
        p["wg"] = truncated_normal(k2, (D, d_ff), std_in, dt)
        s["wg"] = ("embed", "mlp")
    return p, s


def activation(x: jax.Array, act: str) -> jax.Array:
    if act == "silu":
        return jax.nn.silu(x)
    if act == "gelu":
        return jax.nn.gelu(x, approximate=True)
    if act == "relu2":
        return jnp.square(jax.nn.relu(x))
    raise ValueError(act)


def mlp_apply(params: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    dt = x.dtype
    up = jnp.einsum("...d,df->...f", x, params["wi"].astype(dt))
    if cfg.gated_mlp:
        gate = activation(
            jnp.einsum("...d,df->...f", x, params["wg"].astype(dt)), cfg.act
        )
        h = gate * up
    else:
        h = activation(up, cfg.act)
    return jnp.einsum("...f,fd->...d", h, params["wo"].astype(dt))


# ---------------------------------------------------------------------------
# spec utilities
# ---------------------------------------------------------------------------

def stack_specs(specs: Specs) -> Specs:
    """Prepend the scanned 'layers' axis to every leaf spec."""
    return jax.tree.map(
        lambda s: ("layers",) + tuple(s),
        specs,
        is_leaf=lambda s: isinstance(s, tuple) and all(isinstance(x, (str, type(None))) for x in s),
    )


def stack_params(key, n: int, init_one) -> Tuple[Params, Specs]:
    """Initialize n layers and stack each leaf along axis 0 (scan layout).

    Layer i draws from ``fold_in(key, i)``; the layers are initialized as
    one vmapped program (not n unrolled copies, which made the compile of a
    48-layer init take minutes), with the same values."""
    captured = {}

    def one(i):
        p, captured["specs"] = init_one(jax.random.fold_in(key, i))
        return p

    stacked = jax.vmap(one)(jnp.arange(n))
    return stacked, stack_specs(captured["specs"])
