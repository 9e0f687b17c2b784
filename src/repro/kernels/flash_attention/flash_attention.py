"""Pallas TPU flash attention kernel (forward).

Grid (B*H, n_q_blocks, n_kv_blocks); the kv axis is the innermost
('arbitrary') dimension, so the online-softmax accumulators live in VMEM
scratch and persist across kv steps. GQA is done by the K/V BlockSpec
index maps (head h reads kv head h // G) — KV is never repeated in HBM.

VMEM tiling (per grid step):
    q block  (block_q, head_dim)    bf16/fp32
    k block  (block_k, head_dim)
    v block  (block_k, head_dim)
    acc      (block_q, head_dim)    fp32 scratch
    m, l     (block_q, 1)           fp32 scratch

MXU alignment: block_q/block_k multiples of 128, head_dim padded to 128 by
ops.py when needed. Causal/window blocks outside the q block's statically
reachable range are skipped with pl.when (no FLOPs on the skipped path).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, window: Optional[int],
    softcap: Optional[float], block_q: int, block_k: int, nk: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # static-skip bounds are enforced by pl.when on positions:
    q_lo = qi * block_q
    k_lo = ki * block_k
    needed = True
    if causal:
        # any work iff k_lo <= q_hi
        needed = k_lo <= q_lo + block_q - 1
    if window is not None:
        needed = jnp.logical_and(needed, k_lo + block_k - 1 > q_lo - window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32)            # (bq, hd)
        k = k_ref[0].astype(jnp.float32)            # (bk, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                    # (bq, bk)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), dtype=bool)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                          # (bq, 1)
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                       # (bq, bk)
        l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention_fwd(
    q: jax.Array,        # (BH, Sq, hd)
    k: jax.Array,        # (BKV, Skv, hd)
    v: jax.Array,
    *,
    group: int,          # H // KV (BlockSpec head folding)
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    BH, Sq, hd = q.shape
    BKV, Skv, _ = k.shape
    assert BH == BKV * group, (BH, BKV, group)
    assert Sq % block_q == 0 and Skv % block_k == 0, (Sq, Skv, block_q, block_k)
    nq, nk = Sq // block_q, Skv // block_k
    scale = hd ** -0.5

    kernel = functools.partial(
        _fa_kernel,
        scale=scale, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, nk=nk,
    )
    grid = (BH, nq, nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, qi, ki: (b // group, ki, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, qi, ki: (b // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
    )(q, k, v)
