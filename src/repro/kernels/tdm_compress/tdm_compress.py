"""Pallas TPU kernels: blockwise int8 quantization of TDM payloads, plus the
fused receive-side dequant + weighted-accumulate pass.

The ISL (ICI) link is the scarce resource in constellation-scale TDM
exchange (DESIGN.md §3); quantizing gossip payloads to int8 on-chip before
``ppermute`` cuts link bytes 4x. One fused pass per block: absmax reduce ->
scale -> round/clip -> int8 store, blocked to VMEM-sized tiles.

The receive side of the fused exchange engine (:mod:`repro.core.fused`)
accumulates Metropolis-weighted dequantized payloads, one matching at a
time: ``acc += w * (q * scale)``. Doing dequant and accumulate in one kernel
keeps the int8 payload from ever materializing as fp32 in HBM — a single
pass over the buffer per matching.

Layout: a flat buffer of ``nb`` blocks is viewed as ``(nb, sub, lanes)``
— one ``(sub, lanes)`` slab per quantization block, with ``lanes = 128``
whenever ``block`` is a multiple of 128 (else ``lanes = block``). At the
default ``block = 1024`` a slab is one (8, 128) vreg tile, and the view is
a bitcast of the flat array's (1024)-tiled HBM layout: no relayout copy on
the way in or out, for f32 and int8 alike. Each grid step owns ``rows``
whole blocks. Per-block values (scales, top-k values and indices) are
lane-dense: ``(1, nb)`` and ``(k, nb)`` with blocks along lanes, each
tile ``(1, rows)`` / ``(k, rows)`` — a ``(nb, 1)`` layout would pad every
scalar to a 128-lane row. Every reduction (absmax, top-k selection) runs
within one slab, so a block's semantics never depend on the tiling.
The last tile may overhang ``nb``: the rows it reads past the end are
never written back, so no buffer is padded or copied to a multiple of the
tile. Lengths that are not block multiples are zero-padded up to the next
block boundary (zeros never raise a block's absmax, and padded lanes are
sliced off on the way out).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Target elements per tile (512 KiB of f32): large enough that per-step
# overhead stays small, far below the scoped VMEM limit even with every
# operand double-buffered.
_TILE_ELEMS = 128 * 1024
_ROW_ALIGN = 128  # blocks run along lanes in the per-block (k, rows) tiles


def _pad_to_block(x: jax.Array, block: int) -> jax.Array:
    n = x.shape[0]
    pad = (-n) % block
    if pad:
        x = jnp.pad(x, (0, pad))
    return x


def _slab(block: int):
    """``(sub, lanes)`` shape of one block's slab."""
    lanes = 128 if block % 128 == 0 else block
    return block // lanes, lanes


def _rows(nb: int, block: int) -> int:
    """Blocks per tile: a multiple of 128 near ``_TILE_ELEMS``, or ``nb``."""
    rows = max(_ROW_ALIGN, _TILE_ELEMS // block // _ROW_ALIGN * _ROW_ALIGN)
    return nb if nb <= rows else rows


def _slabs(x: jax.Array, block: int) -> jax.Array:
    return x.reshape((x.shape[0] // block,) + _slab(block))


def _tiled_call(
    kernel, name: str, nb: int, block: int, in_widths, out_shapes, interpret,
    aliases=None,
):
    """``pallas_call`` over per-block operands tiled ``rows`` blocks at a time.

    ``name`` is the kernel's stable name in compiled programs and profiles
    (``tdm_quantize``, ``tdm_dequant_acc``, ...), taken from its public
    entry point, so a trace finds the kernel by name after any refactor.

    ``in_widths``/``out_shapes`` describe each operand: ``"slab"`` for the
    ``(nb, sub, lanes)`` payload view, an int ``w`` for a lane-dense
    ``(w, nb)`` per-block array (1 for scales, ``k`` for top-k payloads),
    and ``None`` for the ``(1, 1)`` scalar weight, which every step reads
    whole; out shapes are ``(width, dtype)``. ``aliases`` maps an input to
    the output written in its place (the accumulators update in place: no
    second buffer-sized allocation per matching)."""
    rows = _rows(nb, block)
    sub, lanes = _slab(block)

    def spec(width):
        if width is None:
            return pl.BlockSpec((1, 1), lambda i: (0, 0))
        if width == "slab":
            return pl.BlockSpec((rows, sub, lanes), lambda i: (i, 0, 0))
        return pl.BlockSpec((width, rows), lambda i: (0, i))

    def shape(width, dtype):
        dims = (nb, sub, lanes) if width == "slab" else (width, nb)
        return jax.ShapeDtypeStruct(dims, dtype)

    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(nb, rows),),
        in_specs=[spec(w) for w in in_widths],
        out_specs=[spec(w) for w, _ in out_shapes],
        out_shape=[shape(w, d) for w, d in out_shapes],
        input_output_aliases=aliases or {},
        interpret=interpret,
        name=name,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
    )


def _block_reduce(op, x):
    """Reduce each ``(sub, lanes)`` slab of ``(rows, sub, lanes)`` to
    ``(rows, 1, 1)`` (lanes first, then sublanes)."""
    return op(op(x, axis=2, keepdims=True), axis=1, keepdims=True)


def _to_lanes(v):
    """Per-block ``(rows, 1, 1)`` -> lane-dense ``(1, rows)`` (through a
    128-lane transpose, which Mosaic lowers natively)."""
    col = v[:, 0, :]
    return jnp.broadcast_to(col, (col.shape[0], 128)).T[0:1, :]


def _to_slabs(row):
    """Lane-dense ``(1, rows)`` -> ``(rows, 1, 1)``, broadcastable against
    the ``(rows, sub, lanes)`` slabs."""
    col = jnp.broadcast_to(row, (128, row.shape[1])).T[:, 0:1]
    return col[:, :, None]


def _quant_scaled_kernel(x_ref, s_ref, q_ref):
    x = x_ref[...].astype(jnp.float32)                    # (rows, sub, lanes)
    s = _to_slabs(s_ref[...])                             # (rows, 1, 1)
    q_ref[...] = jnp.clip(jnp.round(x / s), -127, 127).astype(jnp.int8)


def _topk_kernel(k: int, x_ref, dense_ref, v_ref, i_ref):
    """Blockwise top-|x| selection: k rounds of masked argmax per slab.

    Selection key is |x| with NaN ranked above +inf; ties break toward the
    lowest index — the exact order of the stable descending argsort in
    ``topk_sparsify_ref``, so vals/idxs match the oracle elementwise.
    """
    x = x_ref[...].astype(jnp.float32)                    # (rows, sub, lanes)
    rows, sub, lanes = x.shape
    key = jnp.where(jnp.isnan(x), jnp.inf, jnp.abs(x))
    # block-local index of every element
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) * lanes
    col = col + jax.lax.broadcasted_iota(jnp.int32, x.shape, 2)
    out_pos = jax.lax.broadcasted_iota(jnp.int32, (k, rows), 0)

    def body(t, carry):
        live, vals, idxs = carry
        hit = live == _block_reduce(jnp.max, live)
        # lowest index among the slab's maxima (killed lanes hold key -1,
        # below every remaining |x| >= 0, so they can never be re-picked)
        idx_t = _block_reduce(jnp.min, jnp.where(hit, col, sub * lanes))
        chosen = col == idx_t
        v_t = _block_reduce(jnp.sum, jnp.where(chosen, x, 0.0))
        at_t = out_pos == t
        return (
            jnp.where(chosen, -1.0, live),
            jnp.where(at_t, _to_lanes(v_t), vals),
            jnp.where(at_t, _to_lanes(idx_t), idxs),
        )

    init = (
        key,
        jnp.zeros((k, rows), jnp.float32),
        jnp.zeros((k, rows), jnp.int32),
    )
    # the selected lanes are exactly the killed ones (a mask is not carried
    # through the loop: Mosaic cannot hold boolean vectors in loop state)
    live, vals, idxs = jax.lax.fori_loop(0, k, body, init)
    dense_ref[...] = jnp.where(live < 0, x, 0.0)
    v_ref[...] = vals
    i_ref[...] = idxs


def _scatter_acc_kernel(v_ref, i_ref, acc_ref, w_ref, out_ref):
    vals = v_ref[...].astype(jnp.float32)                 # (k, rows)
    idxs = i_ref[...]                                     # (k, rows)
    shape = acc_ref.shape                                 # (rows, sub, lanes)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1) * shape[2]
    col = col + jax.lax.broadcasted_iota(jnp.int32, shape, 2)
    kpos = jax.lax.broadcasted_iota(jnp.int32, idxs.shape, 0)

    def body(t, dense):
        # entry t of every block; the masked sums pick exactly one element
        at_t = kpos == t
        v_t = jnp.sum(jnp.where(at_t, vals, 0.0), axis=0, keepdims=True)
        i_t = jnp.sum(jnp.where(at_t, idxs, 0), axis=0, keepdims=True)
        return dense + jnp.where(col == _to_slabs(i_t), _to_slabs(v_t), 0.0)

    dense = jax.lax.fori_loop(
        0, idxs.shape[0], body, jnp.zeros(shape, jnp.float32)
    )
    out_ref[...] = acc_ref[...] + w_ref[0, 0] * dense


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)                    # (rows, sub, lanes)
    scale = jnp.maximum(_block_reduce(jnp.max, jnp.abs(x)), 1e-12) / 127.0
    q_ref[...] = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    s_ref[...] = _to_lanes(scale)


def _dequant_kernel(q_ref, s_ref, x_ref):
    x_ref[...] = q_ref[...].astype(jnp.float32) * _to_slabs(s_ref[...])


def _dequant_acc_kernel(q_ref, s_ref, acc_ref, w_ref, out_ref):
    out_ref[...] = acc_ref[...] + w_ref[0, 0] * (
        q_ref[...].astype(jnp.float32) * _to_slabs(s_ref[...])
    )


def quantize_fwd(x: jax.Array, *, block: int = 1024, interpret: bool = False):
    """x: flat (n,) any length -> (q int8 (n,), scales fp32 (ceil(n/block),)).

    Lengths that are not block multiples are zero-padded internally; the
    padded tail is sliced off ``q`` (the last scale still reflects only the
    real entries, since zero padding cannot raise the block absmax).
    """
    n = x.shape[0]
    x = _pad_to_block(x, block)
    nb = x.shape[0] // block
    q, s = _tiled_call(
        _quant_kernel, "tdm_quantize", nb, block, ["slab"],
        [("slab", jnp.int8), (1, jnp.float32)],
        interpret,
    )(_slabs(x, block))
    return q.reshape(nb * block)[:n], s.reshape(nb)


def dequantize_fwd(q: jax.Array, scales: jax.Array, *, block: int = 1024,
                   interpret: bool = False):
    """Inverse of :func:`quantize_fwd`; returns fp32 of q's (unpadded) length."""
    n = q.shape[0]
    q = _pad_to_block(q, block)
    nb = q.shape[0] // block
    assert scales.shape[0] == nb, (scales.shape, nb, block)
    (x,) = _tiled_call(
        _dequant_kernel, "tdm_dequantize", nb, block, ["slab", 1],
        [("slab", jnp.float32)],
        interpret,
    )(_slabs(q, block), scales.reshape(1, nb))
    return x.reshape(nb * block)[:n]


def dequant_accumulate_fwd(
    q: jax.Array,
    scales: jax.Array,
    acc: jax.Array,
    w: jax.Array,
    *,
    block: int = 1024,
    interpret: bool = False,
):
    """Fused receive side: ``acc + w * dequant(q, scales)`` in one pass.

    q: integer (n,) — int8 gossip payloads, or the quantize-once relay's
    int16 partial sums; scales: fp32 (ceil(n/block),); acc: fp32 (n,);
    w: scalar (the per-node Metropolis weight of the matching this payload
    arrived on — a traced value inside shard_map). Returns fp32 (n,).
    """
    n = q.shape[0]
    q = _pad_to_block(q, block)
    acc = _pad_to_block(acc.astype(jnp.float32), block)
    nb = q.shape[0] // block
    assert scales.shape[0] == nb, (scales.shape, nb, block)
    w2 = jnp.asarray(w, jnp.float32).reshape(1, 1)
    (out,) = _tiled_call(
        _dequant_acc_kernel, "tdm_dequant_acc", nb, block,
        ["slab", 1, "slab", None],
        [("slab", jnp.float32)],
        interpret,
        aliases={2: 0},
    )(_slabs(q, block), scales.reshape(1, nb), _slabs(acc, block), w2)
    return out.reshape(nb * block)[:n]


def quantize_scaled_fwd(
    x: jax.Array,
    scales: jax.Array,
    *,
    block: int = 1024,
    interpret: bool = False,
):
    """Quantize with caller-supplied blockwise scales (one kernel pass).

    The quantize-once relay contract: every node on a route encodes with
    the SAME shared scales (``pmax`` of the local blockwise scales), so a
    payload pays exactly one quantize/dequant pair end-to-end no matter how
    many hops it rides. x: flat (n,); scales: fp32 (ceil(n/block),),
    strictly positive. Returns q int8 (n,).
    """
    n = x.shape[0]
    x = _pad_to_block(x.astype(jnp.float32), block)
    nb = x.shape[0] // block
    assert scales.shape[0] == nb, (scales.shape, nb, block)
    (q,) = _tiled_call(
        _quant_scaled_kernel, "tdm_quantize_scaled", nb, block, ["slab", 1],
        [("slab", jnp.int8)],
        interpret,
    )(_slabs(x, block), scales.reshape(1, nb))
    return q.reshape(nb * block)[:n]


def topk_sparsify_fwd(
    x: jax.Array,
    k: int,
    *,
    block: int = 1024,
    interpret: bool = False,
):
    """Fused blockwise top-k select+scatter: one pass emits the sparsified
    dense buffer AND the wire payload, no host-side gather.

    x: flat (n,) -> ``(dense (n,) fp32, vals (nb, k) fp32, idxs (nb, k)
    int32 block-local)`` with ``nb = ceil(n/block)``; semantics (selection
    key, NaN/tie order) match :func:`..ref.topk_sparsify_ref` bit-for-bit.
    ``k`` is the static per-block budget, ``0 <= k <= block``.
    """
    if not 0 <= k <= block:
        raise ValueError(f"per-block k must be in [0, {block}], got {k}")
    n = x.shape[0]
    x = _pad_to_block(x.astype(jnp.float32), block)
    nb = x.shape[0] // block
    if k == 0:
        # zero-size VMEM tiles are not a thing; the empty payload is static
        return (
            jnp.zeros((n,), jnp.float32),
            jnp.zeros((nb, 0), jnp.float32),
            jnp.zeros((nb, 0), jnp.int32),
        )
    dense, vals, idxs = _tiled_call(
        functools.partial(_topk_kernel, k), "tdm_topk", nb, block, ["slab"],
        [("slab", jnp.float32), (k, jnp.float32), (k, jnp.int32)],
        interpret,
    )(_slabs(x, block))
    return dense.reshape(nb * block)[:n], vals.T, idxs.T


def scatter_accumulate_fwd(
    vals: jax.Array,
    idxs: jax.Array,
    acc: jax.Array,
    w: jax.Array,
    *,
    block: int = 1024,
    interpret: bool = False,
):
    """Fused top-k receive side: ``acc + w * scatter(vals at idxs)`` in one
    pass over the buffer — the dense contribution never materializes in HBM.

    vals/idxs: (nb, k) as produced by :func:`topk_sparsify_fwd` (indices
    unique within each block row); acc: flat fp32 with
    ``nb = ceil(len(acc)/block)``; w: scalar. Returns fp32 (len(acc),).
    """
    n = acc.shape[0]
    acc = _pad_to_block(acc.astype(jnp.float32), block)
    nb = acc.shape[0] // block
    assert vals.shape == idxs.shape and vals.shape[0] == nb, (
        vals.shape, idxs.shape, nb,
    )
    k = vals.shape[1]
    if k == 0:
        return acc.reshape(nb * block)[:n]
    w2 = jnp.asarray(w, jnp.float32).reshape(1, 1)
    (out,) = _tiled_call(
        _scatter_acc_kernel, "tdm_scatter_acc", nb, block,
        [k, k, "slab", None],
        [("slab", jnp.float32)],
        interpret,
        aliases={2: 0},
    )(vals.T, idxs.T, _slabs(acc, block), w2)
    return out.reshape(nb * block)[:n]
