"""Mixture-of-Experts FFN, in two dispatches (``MoEConfig.dispatch``).

``capacity``: sort-based capacity dispatch (GShard-style groups,
Switch-style capacity), expert-parallel over the mesh ``model`` axis.

Memory-lean dispatch: instead of the (T, E, C) one-hot dispatch tensor we
``argsort`` token->expert assignments and build an (E*C,) gather table of
token indices — O(T·K) integer work, no giant boolean masks. Tokens beyond
an expert's capacity are dropped (their combine weight is zero), standard
for capacity-factor routing.

Grouping: tokens are routed within groups (= batch rows), so the gather
stays local to the data shard; the (G, E, C, D) dispatched tensor is then
resharded expert->model, which lowers to the canonical MoE all-to-all.

``dropless`` (:func:`moe_dropless`): the layer holds ``held`` of the
``n_experts`` its router spans (the chip's share of an expert-parallel
layer) and computes, for every token, only its held experts' part of the
result, with nothing dropped: the assignments to held experts are sorted by
expert into a static buffer, and the experts run as grouped matmuls whose
groups are the held experts' row counts (:func:`grouped_matmul`). The
buffer is the smallest of a short ladder of static sizes
(:func:`size_ladder`) that holds the step's held assignments, chosen on the
device; the last, tokens x min(top_k, held) rows, holds the most that can
arrive. A shared expert, which every chip computes alike, is added to every
token.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.models.layers import activation, dtype_of, init_mlp, mlp_apply, truncated_normal
from repro.launch.sharding import shard_activation


def init_moe(key, cfg: ModelConfig) -> Tuple[Dict, Dict]:
    m = cfg.moe
    if m.dispatch == "dropless":
        return init_dropless(key, cfg)
    D, E, F = cfg.d_model, m.n_experts, m.d_ff
    dt = dtype_of(cfg.param_dtype)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    std_in, std_out = D ** -0.5, F ** -0.5
    p = {
        "router": truncated_normal(k1, (D, E), std_in, jnp.float32),
        "wi": truncated_normal(k2, (E, D, F), std_in, dt),
        "wg": truncated_normal(k3, (E, D, F), std_in, dt),
        "wo": truncated_normal(k4, (E, F, D), std_out, dt),
    }
    s = {
        "router": ("embed", None),
        "wi": ("experts", "embed", "expert_mlp"),
        "wg": ("experts", "embed", "expert_mlp"),
        "wo": ("experts", "expert_mlp", "embed"),
    }
    return p, s


def init_dropless(key, cfg: ModelConfig) -> Tuple[Dict, Dict]:
    """Router over all ``n_experts`` (f32, with a zero correction bias), the
    held experts' weights (ungated: ``act(x wi) wo``), and the shared expert."""
    m = cfg.moe
    D, E, H, F = cfg.d_model, m.n_experts, m.n_held, m.d_ff
    dt = dtype_of(cfg.param_dtype)
    k1, k2, k4, k5 = jax.random.split(key, 4)
    p = {
        "router": truncated_normal(k1, (D, E), D ** -0.5, jnp.float32),
        "router_bias": jnp.zeros((E,), jnp.float32),
        "wi": truncated_normal(k2, (H, D, F), D ** -0.5, dt),
        "wo": truncated_normal(k4, (H, F, D), F ** -0.5, dt),
    }
    s = {
        "router": ("embed", None),
        "router_bias": (None,),
        "wi": ("experts", "embed", "expert_mlp"),
        "wo": ("experts", "expert_mlp", "embed"),
    }
    if m.shared_d_ff:
        p["shared"], s["shared"] = init_mlp(k5, cfg, m.shared_d_ff)
    return p, s


def route(p: Dict, x: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """Top-k of the sigmoid router over all ``n_experts``, in f32: (experts,
    weights), each (T, top_k). The choice ranks the scores plus the
    correction bias; the weights are the chosen scores, normalised to sum
    to 1, then scaled by ``routed_scale``."""
    m = cfg.moe
    scores = jax.nn.sigmoid(jnp.einsum("td,de->te", x.astype(jnp.float32), p["router"]))
    _, top_e = jax.lax.top_k(scores + p["router_bias"], m.top_k)
    top_w = jnp.take_along_axis(scores, top_e, axis=-1)
    top_w = top_w / (top_w.sum(-1, keepdims=True) + 1e-20) * m.routed_scale
    return top_e, top_w


def buffer_rows(tokens: int, cfg: ModelConfig) -> int:
    """Rows of the held experts' static buffer for ``tokens`` tokens: each
    token sends at most min(top_k, held) of its assignments here."""
    m = cfg.moe
    return tokens * min(m.top_k, m.n_held)


GMM_TM = 512  # rows of a grouped-matmul tile


def size_ladder(tokens: int, cfg: ModelConfig) -> Tuple[int, ...]:
    """Static sizes of the held experts' buffer for ``tokens`` tokens, from
    shapes alone: the first is twice the held experts' even share of the
    assignments (tokens x top_k x held / n_experts) rounded up to a
    grouped-matmul tile, each next one doubles the last, and the last is
    :func:`buffer_rows`, the most that can arrive. A single size where the
    first already reaches that bound."""
    m = cfg.moe
    bound = buffer_rows(tokens, cfg)
    size = -(-2 * tokens * m.top_k * m.n_held // (m.n_experts * GMM_TM)) * GMM_TM
    sizes = []
    while size < bound:
        sizes.append(size)
        size *= 2
    return (*sizes, bound)


def step_gauges(tokens: int, cfg: ModelConfig) -> Dict[str, int]:
    """Gauges of a training step of ``tokens`` tokens: ``moe.experts_held``,
    ``moe.expert_rows`` (the bound of one MoE layer's buffer) and
    ``moe.expert_rows_first`` (the first size of its ladder) of a dropless
    MoE; none for a model without one."""
    if cfg.moe is None or cfg.moe.dispatch != "dropless":
        return {}
    return {"moe.experts_held": cfg.moe.n_held, "moe.expert_rows": buffer_rows(tokens, cfg),
            "moe.expert_rows_first": size_ladder(tokens, cfg)[0]}


def _tile(d: int) -> int:
    """The largest multiple of 128 up to 1024 that divides ``d`` (else 128:
    the kernel masks a ragged last tile)."""
    return max((t for t in range(128, 1025, 128) if d % t == 0), default=128)


def grouped_matmul(a: jax.Array, w: jax.Array, sizes: jax.Array) -> jax.Array:
    """Rows of ``a`` (R, K), sorted by group, times their group's ``w``
    (G, K, N); ``sizes`` (G,) counts each group's rows. Rows past the groups
    are undefined. On a TPU this is megablox ``gmm``, which visits only the
    tiles that hold a group's rows: over the dropless buffer it took 2.4x
    less time than ``jax.lax.ragged_dot``, which the TPU compiler runs over
    the whole buffer (PERF.md); elsewhere ``ragged_dot``."""
    if jax.default_backend() != "tpu":
        return jax.lax.ragged_dot(a, w, sizes)
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    R = a.shape[0]
    out = megablox.gmm(
        jnp.pad(a, ((0, -R % GMM_TM), (0, 0))), w, sizes, a.dtype,
        lambda m, k, n: (GMM_TM, _tile(k), _tile(n)),
    )
    return out[:R]


def _held_experts(n: int, cfg: ModelConfig, xt, w, wi, wo, key, order, sizes):
    """The held experts' part of the output, f32 (T, D), over a buffer of the
    first ``n`` assignments in ``order`` (sorted by ``key``: held expert,
    then H for one not held here), which holds every held one."""
    H, K = cfg.moe.n_held, cfg.moe.top_k
    with jax.named_scope(f"rows_{n}"):
        order = order[:n]
        held = (key[order] < H)[:, None]
        rows = order // K

        def grouped(a, wt):
            # the rows past the held assignments are in no group, and the
            # kernel leaves them unwritten, forward and backward: they are
            # masked on the way in (for the gradient) and on the way out
            a = jnp.where(held, a, 0)
            return jnp.where(held, grouped_matmul(a, wt.astype(xt.dtype), sizes), 0)

        h = activation(grouped(xt[rows], wi), cfg.act)
        y = grouped(h, wo).astype(jnp.float32) * w[order][:, None]
        return jnp.zeros(xt.shape, jnp.float32).at[rows].add(y)


def _held_experts_on_ladder(ladder: Tuple[int, ...], cfg: ModelConfig):
    """:func:`_held_experts` over the smallest size of ``ladder`` that holds
    the held assignments, chosen on the device. Its backward switches on the
    same size and stores only the inputs: a ``lax.switch`` under ``grad``
    would keep the residuals of every branch, the full buffer's among them.
    A ladder of one size runs it with no switch."""
    branches = [functools.partial(_held_experts, n, cfg) for n in ladder]
    if len(branches) == 1:
        return branches[0]
    bounds = np.asarray(ladder[:-1], np.int32)  # a constant, in whichever trace

    def choose(sizes):
        return jnp.sum(jnp.sum(sizes) > bounds)

    @jax.custom_vjp
    def run(xt, w, wi, wo, key, order, sizes):
        return jax.lax.switch(choose(sizes), branches, xt, w, wi, wo, key, order, sizes)

    def fwd(*args):
        return run(*args), args

    def bwd(args, g):
        def branch(f):
            def vjp(xt, w, wi, wo, key, order, sizes, g):
                return jax.vjp(lambda *a: f(*a, key, order, sizes), xt, w, wi, wo)[1](g)
            return vjp

        grads = jax.lax.switch(choose(args[-1]), [branch(f) for f in branches], *args, g)
        return (*grads, None, None, None)

    run.defvjp(fwd, bwd)
    return run


def moe_dropless(
    p: Dict, x: jax.Array, cfg: ModelConfig
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: (..., D) -> (the held experts' part of the layer's output plus
    the shared expert's, no auxiliary losses)."""
    m = cfg.moe
    shape, cdt = x.shape, x.dtype
    D, H = shape[-1], m.n_held
    xt = x.reshape(-1, D)
    T = xt.shape[0]
    with jax.named_scope("router"):
        top_e, top_w = route(p, xt, cfg)
    with jax.named_scope("experts"):
        local = top_e.reshape(-1) - m.first_held
        key = jnp.where((local >= 0) & (local < H), local, H)   # H: not held here
        order = jnp.argsort(key, stable=True)
        sizes = jnp.sum(jax.nn.one_hot(key, H, dtype=jnp.int32), axis=0)
        # the expert width padded to a multiple of 128 for the kernel's
        # tiles (the published 1856 is not one): the padded units are 0
        pad = -p["wi"].shape[-1] % 128
        wi = jnp.pad(p["wi"], ((0, 0), (0, 0), (0, pad)))
        wo = jnp.pad(p["wo"], ((0, 0), (0, pad), (0, 0)))
        experts = _held_experts_on_ladder(size_ladder(T, cfg), cfg)
        out = experts(xt, top_w.reshape(-1), wi, wo, key, order, sizes)
    if m.shared_d_ff:
        with jax.named_scope("shared_expert"):
            out = out + mlp_apply(p["shared"], xt, cfg).astype(jnp.float32)
    zero = jnp.zeros((), jnp.float32)
    return out.astype(cdt).reshape(shape), {"moe_aux": zero, "moe_zloss": zero}


def capacity(tokens_per_group: int, cfg: ModelConfig) -> int:
    m = cfg.moe
    c = math.ceil(tokens_per_group * m.top_k * m.capacity_factor / m.n_experts)
    # pad to 8 for clean MXU tiling only when the capacity is already large;
    # decode groups (1 token) must NOT inflate E*C slots 8x (useful-flops!)
    if c >= 8:
        return 8 * math.ceil(c / 8)
    return max(c, 1)


def moe_apply(
    p: Dict, x: jax.Array, cfg: ModelConfig
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: (B, S, D) -> (out (B, S, D), aux losses).

    Groups = batch rows (B); routing, capacity, and the gather/scatter are
    all per-group (local to the data shard).
    """
    m = cfg.moe
    if m.dispatch == "dropless":
        return moe_dropless(p, x, cfg)
    B, S, D = x.shape
    orig_shape = None
    if S == 1 and B > 1:
        # decode regrouping: per-row groups would allocate E*C slots PER ROW
        # (128x wasted expert FLOPs at B=128, E=128); one global group keeps
        # slots ~= tokens * top_k * cf. The token gather crosses data shards
        # but moves only (B, D) bytes — negligible at decode.
        orig_shape = (B, S, D)
        x = x.reshape(1, B, D)
        B, S = 1, B
    E, K = m.n_experts, m.top_k
    C = capacity(S, cfg)
    cdt = x.dtype

    # ---- routing (fp32)
    logits = jnp.einsum(
        "gsd,de->gse", x.astype(jnp.float32), p["router"]
    )                                                   # (B,S,E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, K)              # (B,S,K)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # ---- aux losses (Switch/GShard load balance + router z-loss)
    me = probs.mean(axis=(0, 1))                        # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[top_e.reshape(-1)].add(
        jnp.ones((B * S * K,), jnp.float32)
    ) / (B * S * K)
    aux = E * jnp.sum(me * ce) * m.aux_loss
    zl = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2) * m.router_z_loss

    # ---- sort-based dispatch, per group
    TK = S * K
    expert_flat = top_e.reshape(B, TK)                  # (B, TK)
    w_flat = top_w.reshape(B, TK)
    token_idx = jnp.broadcast_to(
        jnp.arange(S)[:, None], (S, K)
    ).reshape(TK)                                       # (TK,)
    order = jnp.argsort(expert_flat, axis=-1, stable=True)
    sorted_e = jnp.take_along_axis(expert_flat, order, axis=-1)
    sorted_t = token_idx[order]                         # (B, TK)
    sorted_w = jnp.take_along_axis(w_flat, order, axis=-1)
    counts = jax.nn.one_hot(sorted_e, E, dtype=jnp.int32).sum(axis=1)  # (B,E)
    offsets = jnp.cumsum(counts, axis=-1) - counts      # (B,E) exclusive
    rank = jnp.arange(TK)[None, :] - jnp.take_along_axis(offsets, sorted_e, -1)
    keep = rank < C
    slot = jnp.where(keep, sorted_e * C + rank, E * C)  # overflow -> sentinel

    # gather table (B, E*C+1): token index per expert slot, sentinel = S
    table = jnp.full((B, E * C + 1), S, dtype=jnp.int32)
    table = jax.vmap(lambda t, s, tok: t.at[s].set(tok))(table, slot, sorted_t)
    table = table[:, : E * C]
    wtab = jnp.zeros((B, E * C + 1), dtype=jnp.float32)
    wtab = jax.vmap(lambda t, s, w: t.at[s].set(w))(wtab, slot, sorted_w)
    wtab = wtab[:, : E * C]

    # ---- dispatch: (B, E, C, D), expert-sharded
    x_pad = jnp.concatenate([x, jnp.zeros((B, 1, D), cdt)], axis=1)  # sentinel row
    xg = jnp.take_along_axis(
        x_pad, table[:, :, None], axis=1
    ).reshape(B, E, C, D)
    xg = shard_activation(xg, ("batch", "experts", None, None))

    # ---- expert FFN (E-parallel einsums). The hidden constraint makes the
    # tp2d mode explicit: with expert_mlp -> data, h stays F-sharded, the
    # expert weights stay stationary, and the down-proj contraction lowers
    # to an activation psum (no weight all-gathers). Under tp/fsdp modes the
    # constraint maps to replicated-F: a no-op.
    gate = activation(
        jnp.einsum("becd,edf->becf", xg, p["wg"].astype(cdt)), cfg.act
    )
    up = jnp.einsum("becd,edf->becf", xg, p["wi"].astype(cdt))
    h = shard_activation(gate * up, ("batch", "experts", None, "expert_mlp"))
    y = jnp.einsum("becf,efd->becd", h, p["wo"].astype(cdt))
    y = shard_activation(y, ("batch", "experts", None, None))

    # ---- combine: weighted scatter-add back to token order
    y_flat = y.reshape(B, E * C, D) * wtab[:, :, None].astype(cdt)
    out = jnp.zeros((B, S + 1, D), cdt)
    out = jax.vmap(lambda o, t, v: o.at[t].add(v))(out, table, y_flat)
    out = out[:, :S]
    if orig_shape is not None:
        out = out.reshape(orig_shape)
    out = shard_activation(out, ("batch", None, None))
    return out, {"moe_aux": aux, "moe_zloss": zl}
