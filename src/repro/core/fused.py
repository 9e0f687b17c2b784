"""Fused flat-buffer TDM exchange engine: O(matchings) collectives per round.

Motivation (perf): :func:`repro.core.fl.tdm_mix` applied leaf-by-leaf issues
O(L×M) small ``ppermute``s per round for a model with L parameter leaves and
a relation colored into M matchings — collective-launch latency dominates on
real meshes long before the ISL/ICI link saturates. This module flattens the
parameter pytree ONCE per round into dtype-bucketed, block-padded contiguous
buffers, runs the whole mixing step on the fused buffer(s), and unflattens:

    per-leaf:  L×M collective-permutes  (2–3 L×M for compressed payloads)
    fused:       M collective-permutes  (2M int8: payload+scales; M CHOCO —
                 values+indices packed into one int32 payload)

per dtype bucket — for the common all-fp32 model, exactly M. The claim is
HLO-verified in tests (``tests/_fused_worker.py``) and measured by
``benchmarks/fused_exchange.py``.

Numerical contract per compression mode:

- ``none`` (both ``getmeas`` and ``get1meas``): BIT-IDENTICAL to the
  per-leaf path. Mixing is elementwise (per-node scalar weights), so
  gossiping the concatenation equals concatenating the gossips; both paths
  share the very same :func:`repro.core.tdm.gossip_avg` /
  :func:`~repro.core.tdm.gossip_avg_serial` code.
- ``int8``: Metropolis gossip with BLOCKWISE-quantized payloads via the
  Pallas ``tdm_compress`` kernels — quantize once per round, then per
  matching one fused dequant+weighted-accumulate pass over the receive
  buffer. Blockwise scales (one per ``block`` entries) replace the per-leaf
  path's per-tensor scale, so results differ from the per-leaf path by
  quantization granularity only (tighter: a block's absmax ≤ the tensor's).
  The per-leaf path also uses uniform 1/(1+Δ) weights where the fused path
  uses exact Metropolis weights — identical on regular relations.
- ``topk`` (CHOCO-Gossip): the compression state lives on the fused buffer
  and selection is BLOCKWISE over the bucket (the fused ``topk_sparsify``
  kernel picks ``ceil(k_total/nb)`` coordinates per block, one select+
  scatter pass, no host-side gather); the per-round payload budget is
  matched by scaling ``k_total`` to ``topk_k × n_leaves``. Values and
  block-local indices travel PACKED in a single int32 array, so a round
  costs M collective-permutes per bucket — same as uncompressed — and the
  receive side folds each arrival into the CHOCO accumulator with the
  fused ``scatter_accumulate`` kernel. Same convergence guarantees (the
  same CHOCO recursion on the concatenated state); per-round outputs
  differ from per-leaf by which coordinates the budget selects.

All entry points run inside ``shard_map`` over the node axis, like
everything in :mod:`repro.core.tdm`.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tdm
from repro.core.relation import Relation
from repro.telemetry import metrics
from repro.telemetry import recorder as telemetry
from repro.kernels.tdm_compress import ref as q_ref
from repro.kernels.tdm_compress import tdm_compress as q_kernel

DEFAULT_BLOCK = 1024


# ---------------------------------------------------------------------------
# Flat-buffer spec: static (Python-side) layout of a pytree
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LeafSlot:
    """Where one pytree leaf lives inside its dtype bucket's flat buffer."""

    bucket: str                 # canonical dtype name, e.g. "float32"
    offset: int                 # element offset into the bucket buffer
    size: int                   # number of elements
    shape: Tuple[int, ...]


@dataclasses.dataclass(frozen=True)
class FlatSpec:
    """Static layout: leaf -> (bucket, offset) plus padded bucket sizes.

    Buffers are padded to a multiple of ``block`` so the Pallas quantization
    kernels tile them exactly; padding lanes hold zeros and never travel
    back into the tree.
    """

    treedef: Any
    slots: Tuple[LeafSlot, ...]
    bucket_sizes: Tuple[Tuple[str, int], ...]   # (bucket, padded elements)
    bucket_leaves: Tuple[Tuple[str, int], ...]  # (bucket, n leaves)
    block: int

    @property
    def buckets(self) -> List[str]:
        return [b for b, _ in self.bucket_sizes]

    def padded_size(self, bucket: str) -> int:
        return dict(self.bucket_sizes)[bucket]

    def n_leaves(self, bucket: str) -> int:
        return dict(self.bucket_leaves)[bucket]


def build_spec(params: Any, block: int = DEFAULT_BLOCK) -> FlatSpec:
    """Lay out ``params``' leaves into dtype-bucketed contiguous buffers.

    Leaves keep tree order within their bucket; buckets are sorted by dtype
    name so the layout is deterministic for a given tree structure.
    """
    leaves, treedef = jax.tree.flatten(params)
    by_bucket: Dict[str, int] = {}
    counts: Dict[str, int] = {}
    slots = []
    for leaf in leaves:
        bucket = jnp.asarray(leaf).dtype.name
        size = int(np.prod(leaf.shape)) if leaf.shape else 1
        off = by_bucket.get(bucket, 0)
        slots.append(LeafSlot(bucket, off, size, tuple(leaf.shape)))
        by_bucket[bucket] = off + size
        counts[bucket] = counts.get(bucket, 0) + 1
    sizes = tuple(
        (b, -(-by_bucket[b] // block) * block) for b in sorted(by_bucket)
    )
    return FlatSpec(
        treedef=treedef,
        slots=tuple(slots),
        bucket_sizes=sizes,
        bucket_leaves=tuple((b, counts[b]) for b in sorted(by_bucket)),
        block=block,
    )


# Specs are pure functions of (tree structure, leaf shapes/dtypes, block),
# and FL loops re-trace the same model layout for every distinct topology —
# re-deriving the layout per compile is pure waste. Bounded FIFO cache;
# keys hold treedefs and shape tuples only (no arrays, so no device memory).
# Hit/miss stats live on the flight recorder (per run scope, so benchmark
# and test runs cannot leak counts into each other) under this prefix.
_SPEC_CACHE: Dict[Any, FlatSpec] = {}
_SPEC_CACHE_MAX = 128
SPEC_CACHE_COUNTER = "fused.spec_cache"


def _spec_key(params: Any, block: int):
    leaves, treedef = jax.tree.flatten(params)
    return (
        treedef,
        int(block),
        tuple(
            (jnp.asarray(l).dtype.name, tuple(jnp.shape(l))) for l in leaves
        ),
    )


def cached_spec(params: Any, block: int = DEFAULT_BLOCK) -> FlatSpec:
    """:func:`build_spec` behind a cache keyed by (treedef, leaf
    shapes/dtypes, block). Works on tracers and concrete arrays alike —
    the key never touches values, so one layout derivation serves every
    (re)trace of the same model."""
    key = _spec_key(params, block)
    rec = telemetry.get_recorder()
    spec = _SPEC_CACHE.get(key)
    if spec is None:
        rec.counter(f"{SPEC_CACHE_COUNTER}.misses")
        spec = build_spec(params, block=block)
        if len(_SPEC_CACHE) >= _SPEC_CACHE_MAX:
            _SPEC_CACHE.pop(next(iter(_SPEC_CACHE)))
        _SPEC_CACHE[key] = spec
    else:
        rec.counter(f"{SPEC_CACHE_COUNTER}.hits")
    return spec


def spec_cache_stats() -> Dict[str, int]:
    """Hit/miss counts of the ACTIVE run scope (the layout cache itself is
    process-wide; its stats are per-recorder so runs don't leak into each
    other — see :mod:`repro.telemetry.recorder`)."""
    rec = telemetry.get_recorder()
    return {
        "hits": int(rec.get_counter(f"{SPEC_CACHE_COUNTER}.hits")),
        "misses": int(rec.get_counter(f"{SPEC_CACHE_COUNTER}.misses")),
        "size": len(_SPEC_CACHE),
    }


def clear_spec_cache() -> None:
    _SPEC_CACHE.clear()
    telemetry.get_recorder().pop_counters(SPEC_CACHE_COUNTER)


def flatten_pytree(spec: FlatSpec, params: Any) -> Dict[str, jax.Array]:
    """Pytree -> {dtype name: flat padded buffer} (one concatenate per
    bucket), under the ``pack`` name scope."""
    leaves, treedef = jax.tree.flatten(params)
    if treedef != spec.treedef:
        raise ValueError(f"tree mismatch: {treedef} != {spec.treedef}")
    parts: Dict[str, List[jax.Array]] = {b: [] for b in spec.buckets}
    used: Dict[str, int] = {b: 0 for b in spec.buckets}
    out = {}
    with jax.named_scope("pack"):
        for slot, leaf in zip(spec.slots, leaves):
            parts[slot.bucket].append(jnp.asarray(leaf).reshape(-1))
            used[slot.bucket] += slot.size
        for bucket in spec.buckets:
            pad = spec.padded_size(bucket) - used[bucket]
            if pad:
                parts[bucket].append(jnp.zeros((pad,), dtype=jnp.dtype(bucket)))
            out[bucket] = (
                jnp.concatenate(parts[bucket])
                if len(parts[bucket]) > 1
                else parts[bucket][0]
            )
    return out


def unflatten_pytree(spec: FlatSpec, buffers: Dict[str, jax.Array]) -> Any:
    """Inverse of :func:`flatten_pytree` (static slices — free at trace
    time), under the ``unpack`` name scope."""
    leaves = []
    with jax.named_scope("unpack"):
        for slot in spec.slots:
            buf = buffers[slot.bucket]
            leaves.append(
                buf[slot.offset : slot.offset + slot.size].reshape(slot.shape)
            )
    return jax.tree.unflatten(spec.treedef, leaves)


# ---------------------------------------------------------------------------
# Fused buffer mixing
# ---------------------------------------------------------------------------

def _resolve_impl(impl: str) -> str:
    """'auto' -> the Pallas kernels on TPU, their validated jnp oracle
    elsewhere (interpret-mode Pallas is a debugging path, not a hot path)."""
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    if impl not in ("pallas", "pallas_interpret", "ref"):
        raise ValueError(f"unknown quant impl {impl}")
    return impl


def _quantize(x32, block: int, impl: str):
    with jax.named_scope("quantize"):
        if impl == "ref":
            return q_ref.quantize_ref(x32, block=block)
        return q_kernel.quantize_fwd(
            x32, block=block, interpret=(impl == "pallas_interpret")
        )


def _dequant_acc(q, s, acc, w, block: int, impl: str):
    with jax.named_scope("dequant_acc"):
        if impl == "ref":
            return q_ref.dequant_acc_ref(q, s, acc, w, block=block)
        return q_kernel.dequant_accumulate_fwd(
            q, s, acc, w, block=block, interpret=(impl == "pallas_interpret")
        )


def _topk(x32, k: int, block: int, impl: str):
    with jax.named_scope("topk"):
        if impl == "ref":
            return q_ref.topk_sparsify_ref(x32, k, block=block)
        return q_kernel.topk_sparsify_fwd(
            x32, k, block=block, interpret=(impl == "pallas_interpret")
        )


def _scatter_acc(vals, idxs, acc, w, block: int, impl: str):
    with jax.named_scope("scatter_acc"):
        if impl == "ref":
            return q_ref.scatter_acc_ref(vals, idxs, acc, w, block=block)
        return q_kernel.scatter_accumulate_fwd(
            vals, idxs, acc, w, block=block,
            interpret=(impl == "pallas_interpret"),
        )


def int8_gossip(
    x: jax.Array,
    rel: Relation,
    axis_name: str,
    n: int,
    *,
    block: int = DEFAULT_BLOCK,
    impl: str = "auto",
) -> jax.Array:
    """One Metropolis gossip step with blockwise-int8-quantized payloads.

    Send side quantizes ``x`` ONCE (Pallas ``quantize_fwd``); each matching
    then ships (int8 payload, fp32 blockwise scales) = 2 ppermutes, and the
    receive side folds each arrival into the accumulator with the fused
    dequant+weighted-accumulate kernel — a single pass over the buffer per
    matching, no fp32 payload ever materialized.

    ``x`` must be flat with ``len(x) % block == 0`` (the FlatSpec contract).
    """
    if len(rel) == 0:
        return x
    impl = _resolve_impl(impl)
    idx = jax.lax.axis_index(axis_name)
    diag, per_matching = tdm.matching_weight_vectors(rel, n)
    x32 = x.astype(jnp.float32)
    q, scales = _quantize(x32, block, impl)
    acc = jnp.zeros_like(x32)
    matchings = tdm.edge_coloring(rel)
    for m, w_m in zip(matchings, per_matching):
        q_r = tdm.exchange_matching(q, m, axis_name)
        s_r = tdm.exchange_matching(scales, m, axis_name)
        w = jnp.asarray(w_m, jnp.float32)[idx]
        acc = _dequant_acc(q_r, s_r, acc, w, block, impl)
    with jax.named_scope("mix"):
        self_w = jnp.asarray(diag, jnp.float32)[idx]
        return (self_w * x32 + acc).astype(x.dtype)


def choco_fused_round(
    buf: jax.Array,
    state: tdm.ChocoState,
    rel: Relation,
    axis_name: str,
    n: int,
    k_total: int,
    *,
    gamma: float = 0.4,
    block: int = DEFAULT_BLOCK,
    impl: str = "auto",
) -> Tuple[jax.Array, tdm.ChocoState]:
    """One CHOCO-Gossip round on a fused buffer via the fused top-k kernels.

    The same recursion as :func:`repro.core.tdm.choco_gossip_round` (x̂/s
    public-copy state, γ-damped consensus step), lowered onto the
    ``tdm_compress`` kernel family:

    - selection: ONE ``topk_sparsify`` pass picks ``ceil(k_total/nb)``
      coordinates per block and emits the dense sparsified update (for x̂)
      plus the wire payload (vals + block-local idxs) — no argsort/gather on
      the host path;
    - wire: vals are bitcast to int32 and PACKED with the indices into a
      single (nb, 2, k_b) array, so each matching costs ONE
      collective-permute — M per round per bucket, half of the unpacked
      values+indices scheme;
    - receive: each arrival folds into the CHOCO accumulator ``s`` with one
      fused ``scatter_accumulate`` pass (dense contribution never hits HBM).

    State is carried in fp32 regardless of the buffer dtype. Requires
    ``len(buf) % block == 0`` (the FlatSpec contract) and a FIXED relation
    across rounds, like every CHOCO path.
    """
    if buf.shape[0] % block:
        raise ValueError(
            f"fused CHOCO needs a block-padded buffer: {buf.shape[0]} % "
            f"{block} != 0"
        )
    impl = _resolve_impl(impl)
    nb = buf.shape[0] // block
    k_b = max(1, min(block, -(-int(k_total) // nb)))
    idx = jax.lax.axis_index(axis_name)
    x32 = buf.astype(jnp.float32)
    x_hat = state.x_hat.astype(jnp.float32)
    s = state.s.astype(jnp.float32)

    dense_q, vals, idxs = _topk(x32 - x_hat, k_b, block, impl)
    new_x_hat = x_hat + dense_q
    payload = jnp.stack(
        [jax.lax.bitcast_convert_type(vals, jnp.int32), idxs], axis=1
    )  # (nb, 2, k_b): one int32 wire word per payload entry component

    W = tdm.metropolis_weights(rel, n)
    _, per_matching = tdm.matching_weight_vectors(rel, n)
    for m, w_m in zip(tdm.edge_coloring(rel), per_matching):
        p_r = tdm.exchange_matching(payload, m, axis_name)
        v_r = jax.lax.bitcast_convert_type(p_r[:, 0, :], jnp.float32)
        i_r = p_r[:, 1, :]
        w = jnp.asarray(w_m, jnp.float32)[idx]
        s = _scatter_acc(v_r, i_r, s, w, block, impl)

    deg_w = np.zeros((n,), dtype=np.float32)
    for i in range(n):
        deg_w[i] = sum(W[i, j] for j in rel.peers_of(i))
    with jax.named_scope("mix"):
        d_i = jnp.asarray(deg_w, jnp.float32)[idx]
        new_x = x32 + jnp.float32(gamma) * (s - d_i * new_x_hat)
        return new_x.astype(buf.dtype), tdm.ChocoState(x_hat=new_x_hat, s=s)


def mix_wire_bytes(
    n_elems: int,
    itemsize: int,
    compression: str,
    *,
    k: int = 0,
    block: int = DEFAULT_BLOCK,
) -> int:
    """Static wire bytes ONE device ships per matching for one buffer.

    ``none`` ships the raw buffer; ``int8`` ships the quantized buffer plus
    one f32 scale per block (they travel as separate permutes but are one
    matching's payload); ``topk`` ships ``k`` packed (value, block-local
    index) pairs per block — the PR 7 single-payload layout. Per-round
    totals multiply by the relation's matching count; the accounting
    counters in :func:`fused_buffer_mix` do exactly that."""
    nb = -(-int(n_elems) // int(block))
    if compression == "topk":
        return nb * int(k) * 8
    if compression == "int8":
        return int(n_elems) + nb * 4
    return int(n_elems) * int(itemsize)


def _account_exchange(
    rel: Relation, n_elems: int, itemsize: int, compression: str, k: int, block: int
) -> None:
    """Trace-time exchange-size accounting (ISSUE 9 link-layer metrics).

    Runs on the host while the mix is being traced — one bump per
    (topology, layout) COMPILE, not per executed round (per-round rates
    come from multiplying the static per-round counters the drivers keep).
    Zero device ops, so compiled programs and outputs stay bit-identical.
    """
    m = len(tdm.edge_coloring(rel))
    wire = m * mix_wire_bytes(
        n_elems, itemsize, compression, k=k, block=block
    )
    rec = telemetry.get_recorder()
    rec.counter("fused.exchange.mixes_traced")
    rec.counter("fused.exchange.wire_bytes_per_round", wire)
    metrics.observe(
        "fused.exchange.wire_mbytes",
        wire / 1e6,
        buckets=metrics.LOG_BUCKETS,
        rec=rec,
    )


def fused_buffer_mix(
    buf: jax.Array,
    rel: Relation,
    axis_name: str,
    n: int,
    cfg,
    residual: Optional[tdm.ChocoState] = None,
    *,
    n_leaves: int = 1,
    block: int = DEFAULT_BLOCK,
    quant_impl: str = "auto",
) -> Tuple[jax.Array, Optional[tdm.ChocoState]]:
    """One TDM-FLA mixing step for a single fused buffer.

    ``cfg`` is a :class:`repro.core.fl.TDMFLAConfig` (duck-typed to avoid a
    circular import). ``n_leaves`` scales the top-k budget so fused CHOCO
    ships the same payload as the per-leaf path would.
    """
    if len(rel) == 0:
        return buf, residual
    _account_exchange(
        rel,
        buf.shape[0],
        jnp.dtype(buf.dtype).itemsize,
        cfg.compression,
        min(getattr(cfg, "topk_k", 0) * max(n_leaves, 1), buf.shape[0])
        if cfg.compression == "topk"
        else 0,
        block,
    )
    if cfg.compression == "topk":
        k = min(cfg.topk_k * max(n_leaves, 1), buf.shape[0])
        state = (
            residual
            if isinstance(residual, tdm.ChocoState)
            else tdm.choco_init(buf.astype(jnp.float32))
        )
        return choco_fused_round(
            buf, state, rel, axis_name, n, k,
            gamma=cfg.choco_gamma, block=block, impl=quant_impl,
        )
    if cfg.compression == "int8":
        return (
            int8_gossip(
                buf, rel, axis_name, n, block=block, impl=quant_impl
            ),
            residual,
        )
    if cfg.comm == "get1meas":
        return tdm.gossip_avg_serial(buf, rel, axis_name, n), residual
    return tdm.gossip_avg(buf, rel, axis_name, n), residual


def fused_tdm_fla_round(
    params: Any,
    rel: Relation,
    axis_name: str,
    n: int,
    cfg,
    residuals: Any = None,
    *,
    block: int = DEFAULT_BLOCK,
    quant_impl: str = "auto",
) -> Tuple[Any, Any]:
    """One TDM-FLA round over a whole pytree through the fused engine.

    Flatten -> mix each dtype bucket's buffer -> unflatten. Residuals (CHOCO
    state) are keyed by bucket name — an opaque carry; hand back exactly
    what the previous call returned (or None to reset).
    """
    if len(rel) == 0:
        return params, residuals
    spec = cached_spec(params, block=block)
    buffers = flatten_pytree(spec, params)
    res_in = residuals if isinstance(residuals, dict) else {}
    mixed, res_out = {}, {}
    for bucket, buf in buffers.items():
        mixed[bucket], res_out[bucket] = fused_buffer_mix(
            buf,
            rel,
            axis_name,
            n,
            cfg,
            res_in.get(bucket),
            n_leaves=spec.n_leaves(bucket),
            block=block,
            quant_impl=quant_impl,
        )
    return unflatten_pytree(spec, mixed), res_out


# ---------------------------------------------------------------------------
# Hierarchical (pod × data) gossip on fused buffers
# ---------------------------------------------------------------------------

_HIERARCHICAL_COMPRESSIONS = ("none", "int8")


def hierarchical_buffer_mix(
    buf: jax.Array,
    intra_rel: Relation,
    inter_rel: Relation,
    data_axis: str,
    pod_axis: str,
    n_data: int,
    n_pods: int,
    *,
    compression: str = "none",
    block: int = DEFAULT_BLOCK,
    quant_impl: str = "auto",
) -> jax.Array:
    """Two-level TDM mixing of one fused buffer: gossip within each pod over
    ``data_axis`` (dense ICI), then between pods over ``pod_axis`` (the
    sparse optical ISLs) — :func:`repro.core.tdm.hierarchical_gossip`
    lowered onto the fused engine, now including the int8 kernel path
    (quantize once PER LEVEL; each level's matchings ship payload+scales
    through the fused dequant+accumulate kernel).

    ``compression`` must be ``"none"`` or ``"int8"``: topk/CHOCO state is
    tied to one fixed relation and does not fit a two-level schedule.
    """
    if compression not in _HIERARCHICAL_COMPRESSIONS:
        raise ValueError(
            "hierarchical gossip compression must be one of "
            f"{_HIERARCHICAL_COMPRESSIONS}, got {compression!r} (topk/CHOCO "
            "state is tied to one fixed relation, not a two-level schedule)"
        )
    for rel, axis, n_ax in (
        (intra_rel, data_axis, n_data),
        (inter_rel, pod_axis, n_pods),
    ):
        if len(rel) == 0:
            continue
        if compression == "int8":
            buf = int8_gossip(
                buf, rel, axis, n_ax, block=block, impl=quant_impl
            )
        else:
            buf = tdm.gossip_avg(buf, rel, axis, n_ax)
    return buf


def fused_hierarchical_round(
    params: Any,
    intra_rel: Relation,
    inter_rel: Relation,
    data_axis: str,
    pod_axis: str,
    n_data: int,
    n_pods: int,
    *,
    compression: str = "none",
    block: int = DEFAULT_BLOCK,
    quant_impl: str = "auto",
) -> Any:
    """Hierarchical (pod × data) TDM round over a whole pytree through the
    fused engine: flatten once, mix each dtype bucket at both levels,
    unflatten. ``compression="none"`` is bit-identical to per-leaf
    :func:`repro.core.tdm.hierarchical_gossip` (same elementwise gossip on
    the concatenation); static cost is
    ``(M_intra + M_inter) × per × n_buckets`` collective-permutes with
    ``per = 2`` for int8 — the
    :func:`repro.telemetry.expected_hierarchical_collectives` oracle.
    """
    spec = cached_spec(params, block=block)
    buffers = flatten_pytree(spec, params)
    mixed = {
        bucket: hierarchical_buffer_mix(
            buf, intra_rel, inter_rel, data_axis, pod_axis, n_data, n_pods,
            compression=compression, block=block, quant_impl=quant_impl,
        )
        for bucket, buf in buffers.items()
    }
    return unflatten_pytree(spec, mixed)
