"""Exact staged-program FLOPs and a fusion-aware HBM-traffic model, computed
by walking the jaxpr — because XLA's HloCostAnalysis counts while-loop
(= lax.scan) bodies ONCE, which under-counts every scanned model by the
layer count (verified empirically; see EXPERIMENTS.md §Dry-run notes).

FLOPs (exact for the staged program, global shapes):
- dot_general / conv: 2 * M*N*K (batch-aware)
- elementwise: 1 flop per output element; transcendentals tallied separately
- reductions: 1 flop per input element
- scan bodies multiplied by trip count; remat recompute appears naturally in
  the VJP jaxpr and is therefore included (that's the point).

Traffic model (roofline memory term): assumes perfect producer->consumer
fusion of elementwise chains, i.e. bytes move only at
- program inputs/outputs (params, batch, caches) — counted once,
- matmul/conv operands+results,
- gather/scatter/dynamic-slice data,
- scan carries (once per step).
This is the fusion-OPTIMAL floor; real traffic >= this. Dominance decisions
in §Roofline use it together with XLA's (per-body) numbers.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
from jax import core as jcore
from jax.ad_checkpoint import Saveable


ELEMENTWISE = {
    "add", "sub", "mul", "div", "max", "min", "neg", "abs", "floor", "ceil",
    "round", "sign", "and", "or", "xor", "not", "select_n", "clamp",
    "rem", "nextafter", "real", "imag", "integer_pow", "square",
}
TRANSCENDENTAL = {
    "exp", "log", "log1p", "expm1", "tanh", "logistic", "sin", "cos", "tan",
    "rsqrt", "sqrt", "cbrt", "pow", "erf", "erfc", "erf_inv", "atan2",
    "exp2", "lgamma", "digamma",
}
REDUCE = {
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "argmax", "argmin", "cumsum", "cumlogsumexp", "cummax",
    "cumprod",
}
MEMORY_OPS = {
    "gather", "scatter", "scatter-add", "scatter_add", "dynamic_slice",
    "dynamic_update_slice", "sort", "top_k",
}
CALL_PARAM_NAMES = ("jaxpr", "call_jaxpr", "fun_jaxpr")


@dataclass
class Costs:
    flops: float = 0.0
    transcendentals: float = 0.0
    traffic_bytes: float = 0.0

    def __iadd__(self, o: "Costs"):
        self.flops += o.flops
        self.transcendentals += o.transcendentals
        self.traffic_bytes += o.traffic_bytes
        return self

    def scaled(self, k: float) -> "Costs":
        return Costs(self.flops * k, self.transcendentals * k, self.traffic_bytes * k)


def _nbytes(aval) -> float:
    try:
        return float(math.prod(aval.shape) * aval.dtype.itemsize)
    except Exception:
        return 0.0


def _nelems(aval) -> float:
    try:
        return float(math.prod(aval.shape))
    except Exception:
        return 0.0


def _dot_flops(eqn) -> float:
    (contract, batch) = eqn.params["dimension_numbers"]
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    lc, rc = contract
    lb, rb = batch
    batch_sz = math.prod(lhs.shape[i] for i in lb) if lb else 1
    k = math.prod(lhs.shape[i] for i in lc) if lc else 1
    m = math.prod(
        lhs.shape[i] for i in range(len(lhs.shape)) if i not in set(lc) | set(lb)
    )
    n = math.prod(
        rhs.shape[i] for i in range(len(rhs.shape)) if i not in set(rc) | set(rb)
    )
    return 2.0 * batch_sz * m * n * k


def _conv_flops(eqn) -> float:
    out = eqn.outvars[0].aval
    rhs = eqn.invars[1].aval
    # 2 * out_elems * (kernel spatial * in_features)
    kernel = math.prod(rhs.shape[:-1])
    return 2.0 * _nelems(out) * kernel


def jaxpr_costs(jaxpr: jcore.Jaxpr) -> Costs:
    total = Costs()
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        # --- control flow / calls
        if prim == "scan":
            inner = jaxpr_costs(eqn.params["jaxpr"].jaxpr)
            length = eqn.params["length"]
            body = inner.scaled(length)
            # carry traffic once per step
            n_carry = eqn.params["num_carry"]
            carry_bytes = sum(_nbytes(v.aval) for v in eqn.invars[
                eqn.params["num_consts"]: eqn.params["num_consts"] + n_carry
            ])
            body.traffic_bytes += carry_bytes * length
            total += body
            continue
        if prim == "while":
            inner = Costs()
            inner += jaxpr_costs(eqn.params["body_jaxpr"].jaxpr)
            total += inner  # trip count unknown: count once (we never use raw while)
            continue
        if prim == "cond":
            branches = [jaxpr_costs(b.jaxpr) for b in eqn.params["branches"]]
            worst = max(branches, key=lambda c: c.flops) if branches else Costs()
            total += worst
            continue
        handled_call = False
        for name in CALL_PARAM_NAMES:
            sub = eqn.params.get(name)
            if sub is None:
                continue
            inner = sub.jaxpr if hasattr(sub, "jaxpr") else sub  # Closed or raw
            if hasattr(inner, "eqns"):
                total += jaxpr_costs(inner)
                handled_call = True
                break
        if handled_call:
            continue
        if prim == "custom_vjp_call":
            # fwd costs only; bwd shows up in the grad jaxpr itself
            call = eqn.params.get("call_jaxpr")
            if call is not None:
                total += jaxpr_costs(call.jaxpr)
            continue
        # --- compute ops
        if prim == "dot_general":
            fl = _dot_flops(eqn)
            total.flops += fl
            total.traffic_bytes += (
                _nbytes(eqn.invars[0].aval)
                + _nbytes(eqn.invars[1].aval)
                + _nbytes(eqn.outvars[0].aval)
            )
            continue
        if prim == "conv_general_dilated":
            total.flops += _conv_flops(eqn)
            total.traffic_bytes += sum(_nbytes(v.aval) for v in eqn.invars)
            total.traffic_bytes += _nbytes(eqn.outvars[0].aval)
            continue
        if prim in ELEMENTWISE:
            total.flops += _nelems(eqn.outvars[0].aval)
            continue
        if prim in TRANSCENDENTAL:
            n = _nelems(eqn.outvars[0].aval)
            total.flops += n
            total.transcendentals += n
            continue
        if prim in REDUCE:
            total.flops += _nelems(eqn.invars[0].aval)
            continue
        if prim in MEMORY_OPS:
            total.traffic_bytes += sum(_nbytes(v.aval) for v in eqn.invars)
            total.traffic_bytes += sum(_nbytes(v.aval) for v in eqn.outvars)
            continue
        # everything else: free (reshape/transpose/broadcast fuse away)
    return total


def program_costs(fn, *args, **kwargs) -> Costs:
    """Costs of fn(*args) plus top-level I/O traffic (params read, outputs
    written, donated caches rewritten)."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    c = jaxpr_costs(closed.jaxpr)
    io_bytes = sum(_nbytes(v.aval) for v in closed.jaxpr.invars)
    io_bytes += sum(_nbytes(v.aval) for v in closed.jaxpr.outvars)
    c.traffic_bytes += io_bytes
    return c


def remat_saved_bytes(fn, *args, **kwargs) -> float:
    """Bytes that the remat policies in ``fn(*args)``'s forward keep for the
    backward, beyond each checkpoint's inputs: the outputs of the
    equations a checkpoint's policy marks saveable, times the trip counts
    of the scans around them. A checkpoint without a policy keeps nothing
    more, and a model outside any checkpoint reads 0."""
    return _saved_bytes(jax.make_jaxpr(fn)(*args, **kwargs).jaxpr, None)


def _saved_bytes(jaxpr: jcore.Jaxpr, policy) -> float:
    total = 0.0
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim == "scan":
            total += eqn.params["length"] * _saved_bytes(eqn.params["jaxpr"].jaxpr, policy)
            continue
        if prim == "remat2":  # jax.checkpoint
            total += _saved_bytes(eqn.params["jaxpr"], eqn.params["policy"])
            continue
        sub = next((eqn.params[n] for n in CALL_PARAM_NAMES if n in eqn.params), None)
        if sub is not None:
            total += _saved_bytes(getattr(sub, "jaxpr", sub), policy)
            continue
        if policy is not None:
            saved = policy(eqn.primitive, *(v.aval for v in eqn.invars), **eqn.params)
            if saved is True or saved is Saveable:
                total += sum(_nbytes(v.aval) for v in eqn.outvars)
    return total
