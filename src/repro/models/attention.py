"""Blocked (flash-style) attention in pure JAX — the XLA path of the model
substrate, shared by every attention-bearing assigned architecture.

Design (DESIGN.md §7): O(S) memory online-softmax attention with

- GQA (grouped einsums — KV heads are never materialized H times),
- causal masking with STATIC block skipping (the strictly-upper-triangle
  blocks are never computed, so ``cost_analysis`` FLOPs reflect the real
  work — no masked-but-counted waste),
- sliding-window (gemma2 local layers; jamba long-context) with static
  block-range restriction,
- attention logit softcapping (gemma2),
- a manual flash backward (``custom_vjp``): forward saves only (out, lse);
  backward recomputes probabilities blockwise from the saved lse.

On a TPU, ``flash_attention_train`` runs the bundled Pallas splash-attention
kernels instead, forward and backward, wherever they implement the spec and
the shapes tile (:func:`kernel_applies`): each score tile then stays in
VMEM, where the blocked path above makes it an XLA array in HBM.
``naive_attention`` here is the semantic oracle for every path.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class AttnSpec:
    causal: bool = True
    window: Optional[int] = None      # sliding-window size (None = unbounded)
    softcap: Optional[float] = None   # attention-logit softcap (gemma2: 50.0)
    block_q: int = 512
    block_k: int = 1024

    def scale(self, head_dim: int) -> float:
        return head_dim ** -0.5


NEG_INF = -1e30


def _softcap(scores: jax.Array, cap: Optional[float]) -> jax.Array:
    if cap is None:
        return scores
    return cap * jnp.tanh(scores / cap)


# ---------------------------------------------------------------------------
# naive oracle
# ---------------------------------------------------------------------------

def naive_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    spec: AttnSpec,
    q_offset=0,
    kv_len: Optional[jax.Array] = None,
) -> jax.Array:
    """Materialized-scores reference. q: (B,Sq,H,hd); k,v: (B,Skv,KV,hd)."""
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    q5 = q.reshape(B, Sq, KV, G, hd)
    scores = jnp.einsum(
        "bqkgh,bskh->bkgqs", q5, k, preferred_element_type=jnp.float32
    ) * spec.scale(hd)
    scores = _softcap(scores, spec.softcap)
    qpos = q_offset + jnp.arange(Sq)[:, None]          # (Sq, 1)
    kpos = jnp.arange(Skv)[None, :]                     # (1, Skv)
    mask = jnp.ones((Sq, Skv), dtype=bool)
    if spec.causal:
        mask &= kpos <= qpos
    if spec.window is not None:
        mask &= kpos > qpos - spec.window
    if kv_len is not None:
        mask &= kpos < kv_len
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", p.astype(v.dtype), v)
    return out.reshape(B, Sq, H, hd)


# ---------------------------------------------------------------------------
# static block-range logic
# ---------------------------------------------------------------------------

def _kv_block_range(
    qi: int, spec: AttnSpec, Sq: int, Skv: int, nk_total: int
) -> Tuple[int, int]:
    """[lo_blk, hi_blk) of kv blocks q block ``qi`` touches (train/prefill
    path: q_offset == 0 and Sq == Skv when causal)."""
    bq, bk = spec.block_q, spec.block_k
    q_lo, q_hi = qi * bq, min((qi + 1) * bq, Sq) - 1
    lo, hi = 0, Skv
    if spec.causal:
        hi = min(hi, q_hi + 1)
    if spec.window is not None:
        lo = max(lo, q_lo - spec.window + 1)
    lo_blk = lo // bk
    hi_blk = -(-hi // bk)  # ceil
    return lo_blk, min(hi_blk, nk_total)


def _block_mask(
    q_pos: jax.Array, k_pos: jax.Array, spec: AttnSpec, kv_len
) -> jax.Array:
    """(bq, bk) bool mask for one (q block, kv block) pair."""
    qp, kp = q_pos[:, None], k_pos[None, :]
    mask = jnp.ones(qp.shape[:1] + kp.shape[1:], dtype=bool)
    if spec.causal:
        mask &= kp <= qp
    if spec.window is not None:
        mask &= kp > qp - spec.window
    if kv_len is not None:
        mask &= kp < kv_len
    return mask


# ---------------------------------------------------------------------------
# forward core: one q block, scanning its kv range
# ---------------------------------------------------------------------------

def _fwd_one_q_block(
    q_blk: jax.Array,      # (B, KV, G, bq, hd)
    k_sub: jax.Array,      # (B, kv_span, KV, hd)
    v_sub: jax.Array,
    q_pos: jax.Array,      # (bq,) absolute positions
    k_pos0: int | jax.Array,
    spec: AttnSpec,
    kv_len,
    needs_mask: bool,
) -> Tuple[jax.Array, jax.Array]:
    """Online-softmax over kv blocks. Returns (out_blk (B,KV,G,bq,hd), lse)."""
    B, KV, G, bq, hd = q_blk.shape
    span = k_sub.shape[1]
    bk = spec.block_k
    nk = span // bk
    scale = spec.scale(hd)

    def body(carry, i):
        m, l, acc = carry
        k_blk = jax.lax.dynamic_slice_in_dim(k_sub, i * bk, bk, axis=1)
        v_blk = jax.lax.dynamic_slice_in_dim(v_sub, i * bk, bk, axis=1)
        s = jnp.einsum(
            "bkgqh,btkh->bkgqt", q_blk, k_blk, preferred_element_type=jnp.float32
        ) * scale
        s = _softcap(s, spec.softcap)
        if needs_mask or kv_len is not None:
            k_pos = k_pos0 + i * bk + jnp.arange(bk)
            mask = _block_mask(q_pos, k_pos, spec, kv_len)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        pv = jnp.einsum(
            "bkgqt,btkh->bkgqh", p.astype(v_blk.dtype), v_blk,
            preferred_element_type=jnp.float32,
        )
        acc_new = acc * alpha[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KV, G, bq), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((B, KV, G, bq), dtype=jnp.float32)
    acc0 = jnp.zeros((B, KV, G, bq, hd), dtype=jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), jnp.arange(nk))
    l_safe = jnp.maximum(l, 1e-30)
    out = (acc / l_safe[..., None]).astype(q_blk.dtype)
    lse = m + jnp.log(l_safe)
    return out, lse


def _flash_forward(q, k, v, spec: AttnSpec, kv_len=None):
    """Unrolled loop over q blocks; each q block scans only the kv blocks its
    (causal, window) range statically requires."""
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    bq, bk = spec.block_q, spec.block_k
    nq, nk = Sq // bq, Skv // bk
    q5 = q.reshape(B, Sq, KV, G, hd).transpose(0, 2, 3, 1, 4)  # (B,KV,G,Sq,hd)

    outs, lses = [], []
    for qi in range(nq):
        lo_blk, hi_blk = _kv_block_range(qi, spec, Sq, Skv, nk)
        q_blk = jax.lax.slice_in_dim(q5, qi * bq, (qi + 1) * bq, axis=3)
        k_sub = jax.lax.slice_in_dim(k, lo_blk * bk, hi_blk * bk, axis=1)
        v_sub = jax.lax.slice_in_dim(v, lo_blk * bk, hi_blk * bk, axis=1)
        q_pos = qi * bq + jnp.arange(bq)
        # masking needed only when the block range boundary cuts a block
        needs_mask = spec.causal or spec.window is not None
        out_blk, lse_blk = _fwd_one_q_block(
            q_blk, k_sub, v_sub, q_pos, lo_blk * bk, spec, kv_len, needs_mask
        )
        outs.append(out_blk)
        lses.append(lse_blk)
    out = jnp.concatenate(outs, axis=3)   # (B,KV,G,Sq,hd)
    lse = jnp.concatenate(lses, axis=3)   # (B,KV,G,Sq)
    out_b = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd)
    return out_b, (out, lse)


# ---------------------------------------------------------------------------
# manual flash backward
# ---------------------------------------------------------------------------

def _flash_backward(q, k, v, out5, lse, g, spec: AttnSpec):
    """Recompute-probabilities backward.

    q: (B,Sq,H,hd) primal; out5/lse: (B,KV,G,Sq,·) saved; g: (B,Sq,H,hd).
    Returns (dq, dk, dv) with the same static block structure as forward.
    """
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    bq, bk = spec.block_q, spec.block_k
    nq, nk = Sq // bq, Skv // bk
    scale = spec.scale(hd)

    q5 = q.reshape(B, Sq, KV, G, hd).transpose(0, 2, 3, 1, 4)
    g5 = g.reshape(B, Sq, KV, G, hd).transpose(0, 2, 3, 1, 4)
    # D_i = sum_h dOut_i * Out_i  (per row)
    delta = jnp.sum(g5.astype(jnp.float32) * out5.astype(jnp.float32), axis=-1)

    dq5 = jnp.zeros_like(q5, dtype=jnp.float32)
    dk = jnp.zeros_like(k, dtype=jnp.float32)
    dv = jnp.zeros_like(v, dtype=jnp.float32)

    for qi in range(nq):
        lo_blk, hi_blk = _kv_block_range(qi, spec, Sq, Skv, nk)
        span = (hi_blk - lo_blk) * bk
        q_blk = jax.lax.slice_in_dim(q5, qi * bq, (qi + 1) * bq, axis=3)
        g_blk = jax.lax.slice_in_dim(g5, qi * bq, (qi + 1) * bq, axis=3)
        lse_blk = jax.lax.slice_in_dim(lse, qi * bq, (qi + 1) * bq, axis=3)
        dlt_blk = jax.lax.slice_in_dim(delta, qi * bq, (qi + 1) * bq, axis=3)
        k_sub = jax.lax.slice_in_dim(k, lo_blk * bk, hi_blk * bk, axis=1)
        v_sub = jax.lax.slice_in_dim(v, lo_blk * bk, hi_blk * bk, axis=1)
        q_pos = qi * bq + jnp.arange(bq)

        def body(carry, i):
            dq_acc, dk_sub, dv_sub = carry
            k_blk = jax.lax.dynamic_slice_in_dim(k_sub, i * bk, bk, axis=1)
            v_blk = jax.lax.dynamic_slice_in_dim(v_sub, i * bk, bk, axis=1)
            s_raw = jnp.einsum(
                "bkgqh,btkh->bkgqt", q_blk, k_blk,
                preferred_element_type=jnp.float32,
            ) * scale
            if spec.softcap is not None:
                t = jnp.tanh(s_raw / spec.softcap)
                s = spec.softcap * t
                dcap = 1.0 - t * t      # d softcap(s)/ds
            else:
                s = s_raw
                dcap = None
            k_pos = lo_blk * bk + i * bk + jnp.arange(bk)
            mask = _block_mask(q_pos, k_pos, spec, None)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            p = jnp.exp(s - lse_blk[..., None])          # (B,KV,G,bq,bk)
            dp = jnp.einsum(
                "bkgqh,btkh->bkgqt", g_blk.astype(jnp.float32),
                v_blk.astype(jnp.float32), preferred_element_type=jnp.float32,
            )
            ds = p * (dp - dlt_blk[..., None])           # d wrt softcapped s
            if dcap is not None:
                ds = ds * dcap
            ds = ds * scale
            dq_acc = dq_acc + jnp.einsum(
                "bkgqt,btkh->bkgqh", ds, k_blk.astype(jnp.float32),
                preferred_element_type=jnp.float32,
            )
            dk_blk = jnp.einsum(
                "bkgqt,bkgqh->btkh", ds, q_blk.astype(jnp.float32),
                preferred_element_type=jnp.float32,
            )
            dv_blk = jnp.einsum(
                "bkgqt,bkgqh->btkh", p, g_blk.astype(jnp.float32),
                preferred_element_type=jnp.float32,
            )
            dk_sub = jax.lax.dynamic_update_slice_in_dim(
                dk_sub, jax.lax.dynamic_slice_in_dim(dk_sub, i * bk, bk, 1) + dk_blk,
                i * bk, axis=1,
            )
            dv_sub = jax.lax.dynamic_update_slice_in_dim(
                dv_sub, jax.lax.dynamic_slice_in_dim(dv_sub, i * bk, bk, 1) + dv_blk,
                i * bk, axis=1,
            )
            return (dq_acc, dk_sub, dv_sub), None

        nk_q = span // bk
        dq0 = jnp.zeros_like(q_blk, dtype=jnp.float32)
        dk_sub0 = jnp.zeros((B, span, KV, hd), dtype=jnp.float32)
        dv_sub0 = jnp.zeros((B, span, KV, hd), dtype=jnp.float32)
        (dq_blk, dk_sub, dv_sub), _ = jax.lax.scan(
            body, (dq0, dk_sub0, dv_sub0), jnp.arange(nk_q)
        )
        dq5 = jax.lax.dynamic_update_slice_in_dim(dq5, dq_blk, qi * bq, axis=3)
        dk = jax.lax.dynamic_update_slice_in_dim(
            dk, jax.lax.dynamic_slice_in_dim(dk, lo_blk * bk, span, 1) + dk_sub,
            lo_blk * bk, axis=1,
        )
        dv = jax.lax.dynamic_update_slice_in_dim(
            dv, jax.lax.dynamic_slice_in_dim(dv, lo_blk * bk, span, 1) + dv_sub,
            lo_blk * bk, axis=1,
        )

    dq = dq5.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd).astype(q.dtype)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def _divisible(q_shape, k_shape, spec: AttnSpec) -> bool:
    return (
        q_shape[1] % spec.block_q == 0
        and k_shape[1] % spec.block_k == 0
        and q_shape[1] >= spec.block_q
        and k_shape[1] >= spec.block_k
    )


def kernel_applies(q_shape, k_shape, spec: AttnSpec) -> bool:
    """Whether ``flash_attention_train`` runs the fused Pallas kernel for
    q of ``q_shape`` (B, Sq, H, hd) and k of ``k_shape`` (B, Skv, KV, hd):
    on a TPU, for a causal or full spec (no window, no softcap), with
    ``head_dim`` a multiple of 128, ``H`` a multiple of ``KV``, and both
    lengths whole numbers of the spec's blocks, which are multiples of 128.
    Elsewhere the blocked pure-JAX path runs."""
    _, _, H, hd = q_shape
    KV = k_shape[2]
    return (
        jax.default_backend() == "tpu"
        and spec.window is None
        and spec.softcap is None
        and hd % 128 == 0
        and H % KV == 0
        and spec.block_q % 128 == 0
        and spec.block_k % 128 == 0
        and _divisible(q_shape, k_shape, spec)
    )


@functools.lru_cache(maxsize=32)
def _splash_kernel(Sq: int, Skv: int, H: int, causal: bool, bq: int, bk: int,
                   interpret: bool):
    """The splash-attention kernel for one example: q (H, Sq, hd) against
    k, v (KV, Skv, hd), KV heads read by index map (head h reads KV head
    h // (H // KV)). Causally empty kv blocks are skipped, in the forward
    and in both backward passes (``dq``, ``dkv``). Made once per shape, as
    concrete arrays, whatever trace asks first."""
    from jax.experimental.pallas.ops.tpu import splash_attention as splash

    mask_cls = splash.CausalMask if causal else splash.FullMask
    mask = splash.MultiHeadMask([mask_cls((Sq, Skv))] * H)
    sizes = splash.BlockSizes(
        block_q=bq, block_kv=bk,
        block_q_dkv=bq, block_kv_dkv=bk,
        block_q_dq=bq, block_kv_dq=bk,
    )
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mha_single_device(
            mask, block_sizes=sizes, interpret=interpret
        )


def splash_attention(q, k, v, spec: AttnSpec, interpret: bool = False):
    """Attention through the Pallas splash kernels: q (B, Sq, H, hd), k, v
    (B, Skv, KV, hd). The MXU operands stay in the inputs' dtype with f32
    accumulation and f32 softmax statistics; the kernel applies no scale, so
    q is scaled in f32 and rounded once. Differentiable: the kernels'
    forward saves (out, lse) and the backward runs their ``dq`` and ``dkv``
    passes."""
    _, Sq, H, hd = q.shape
    kernel = _splash_kernel(Sq, k.shape[1], H, spec.causal, spec.block_q,
                            spec.block_k, interpret)
    qs = (q.astype(jnp.float32) * spec.scale(hd)).astype(q.dtype)
    heads_major = lambda x: x.transpose(0, 2, 1, 3)
    out = jax.vmap(kernel)(heads_major(qs), heads_major(k), heads_major(v))
    return heads_major(out)


def _attention_by_ad(q, k, spec: AttnSpec):
    """The attention that ``flash_attention_train`` differentiates by AD, or
    None for the blocked pure-JAX path and its manual backward: the splash
    kernels where :func:`kernel_applies` (their own forward saves (out, lse)
    and their backward runs the ``dq``/``dkv`` passes), the naive oracle for
    shapes that don't tile (tiny smoke configs)."""
    if kernel_applies(q.shape, k.shape, spec):
        return splash_attention
    if not _divisible(q.shape, k.shape, spec):
        return naive_attention
    return None


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def flash_attention_train(q, k, v, spec: AttnSpec):
    """Training attention (q_offset=0): the Pallas splash kernels where
    :func:`kernel_applies`, else the blocked pure-JAX path, which falls back
    to the naive oracle for shapes that don't tile (tiny smoke configs)."""
    attend = _attention_by_ad(q, k, spec)
    if attend is not None:
        return attend(q, k, v, spec)
    out, _ = _flash_forward(q, k, v, spec)
    return out


def _fa_fwd(q, k, v, spec: AttnSpec):
    attend = _attention_by_ad(q, k, spec)
    if attend is not None:
        out, vjp = jax.vjp(lambda q, k, v: attend(q, k, v, spec), q, k, v)
        return out, (None, vjp)
    out, (out5, lse) = _flash_forward(q, k, v, spec)
    return out, ((q, k, v, out5, lse), None)


def _fa_bwd(spec: AttnSpec, res, g):
    saved, vjp = res
    if saved is None:
        return vjp(g)
    q, k, v, out5, lse = saved
    return _flash_backward(q, k, v, out5, lse, g, spec)


flash_attention_train.defvjp(_fa_fwd, _fa_bwd)


def flash_attention_decode(q, k, v, spec: AttnSpec, q_offset, kv_len=None):
    """Decode attention against a (possibly padded) KV cache. Sq is tiny
    (usually 1); ``q_offset`` may be a traced scalar (decode position), so
    the kv block range cannot be statically narrowed — every cache block is
    computed and masked by ``kv_len``, the honest worst case for a serving
    step at full context. Prefill should use ``flash_attention_train``
    (q_offset = 0 ⇒ identical semantics, static block skipping)."""
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    bk = spec.block_k if Skv % spec.block_k == 0 else Skv
    q5 = q.reshape(B, Sq, KV, G, hd).transpose(0, 2, 3, 1, 4)
    q_pos = q_offset + jnp.arange(Sq)
    out_blk, _ = _fwd_one_q_block(
        q5,
        k,
        v,
        q_pos,
        0,
        AttnSpec(
            causal=spec.causal,
            window=spec.window,
            softcap=spec.softcap,
            block_q=Sq,
            block_k=bk,
        ),
        kv_len,
        needs_mask=True,
    )
    return out_blk.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd)
