"""Pallas TPU kernel for the Mamba-2 SSD chunked scan.

Grid (BH, n_chunks); the chunk axis is 'arbitrary' (sequential), carrying
the (P, N) recurrent state in VMEM scratch. Each chunk step is three
MXU matmuls ((Q,N)x(N,Q), (Q,Q)x(Q,P), (P,Q)x(Q,N)) plus elementwise decay
math — exactly the structure of models/mamba2.ssd_chunked, one (batch·head)
per grid row.

VMEM tiling per step: x (Q,P), B/C (Q,N), dt rows (Q,1), state (P,N),
L-matrix (Q,Q). With Q=P=64..256 and N=128 everything is MXU-aligned.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(
    x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_out_ref, state_scr,
    *, chunk: int, nc: int,
):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0].astype(jnp.float32)          # (Q, P)
    dt = dt_ref[0].astype(jnp.float32)        # (Q, 1)
    A = a_ref[0].astype(jnp.float32)          # (1,) per-head decay coeff
    B = b_ref[0].astype(jnp.float32)          # (Q, N)
    C = c_ref[0].astype(jnp.float32)          # (Q, N)

    l = dt * A                                 # (Q,1) negative decays
    cum = jnp.cumsum(l, axis=0)                # (Q,1) inclusive
    cum_last = cum[-1:]                        # (1,1)

    # inter-chunk: y_t += exp(cum_t) * C_t . S_prev
    state = state_scr[...]                     # (P, N)
    y_inter = jnp.exp(cum) * jax.lax.dot_general(
        C, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                          # (Q, P)

    # intra-chunk: W[t,s] = (C_t.B_s) exp(cum_t - cum_s) dt_s ; s <= t
    CB = jax.lax.dot_general(
        C, B, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                          # (Q, Q)
    Ldec = jnp.exp(cum - cum.T)                # (Q, Q): exp(cum_t - cum_s)
    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    W = jnp.where(si <= ti, CB * Ldec, 0.0) * dt.T
    y_intra = jax.lax.dot_general(
        W, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                          # (Q, P)

    y_ref[0] = (y_inter + y_intra).astype(y_ref.dtype)

    # state update: S = exp(cum_Q) S + sum_s exp(cum_Q - cum_s) dt_s x_s B_s^T
    decay_to_end = jnp.exp(cum_last - cum) * dt            # (Q,1)
    xw = x * decay_to_end                                   # (Q,P)
    new_state = jnp.exp(cum_last) * state + jax.lax.dot_general(
        xw, B, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                          # (P, N)
    state_scr[...] = new_state

    @pl.when(ci == nc - 1)
    def _flush():
        state_out_ref[0] = new_state


def ssd_scan_fwd(
    x: jax.Array,      # (BH, S, P)
    dt: jax.Array,     # (BH, S, 1) fp32
    A: jax.Array,      # (BH, 1) fp32 negative
    B: jax.Array,      # (BH, S, N)
    C: jax.Array,      # (BH, S, N)
    *,
    chunk: int = 128,
    interpret: bool = False,
):
    BH, S, P = x.shape
    N = B.shape[-1]
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk

    kernel = functools.partial(_ssd_kernel, chunk=chunk, nc=nc)
    y, final_state = pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, 1), lambda b, ci: (b, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, ci: (b, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, P), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, P, N), lambda b, ci: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, P), x.dtype),
            jax.ShapeDtypeStruct((BH, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
    )(x, dt, A, B, C)
    return y, final_state
