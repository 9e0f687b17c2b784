"""Model FLOPs of a training step, counted from the forward pass's jaxpr.

The walker follows ``repro.launch.flops.jaxpr_costs`` (scan bodies times
their trip count, calls and remat regions entered once), but counts only
matrix multiplications and convolutions, the work the chip's bf16 peak
describes, and walks the forward pass alone: training FLOPs are forward x 3
(forward, and a backward that costs twice the forward), so recompute that
remat adds to the backward is never counted.
"""

from __future__ import annotations

import math

import jax

CALL_PARAMS = ("jaxpr", "call_jaxpr", "fun_jaxpr")


def _dot_flops(eqn) -> float:
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    lhs, rhs = eqn.invars[0].aval.shape, eqn.invars[1].aval.shape
    batch = math.prod(lhs[i] for i in lb)
    k = math.prod(lhs[i] for i in lc)
    m = math.prod(d for i, d in enumerate(lhs) if i not in set(lc) | set(lb))
    n = math.prod(d for i, d in enumerate(rhs) if i not in set(rc) | set(rb))
    return 2.0 * batch * m * n * k


def _conv_flops(eqn) -> float:
    out = eqn.outvars[0].aval.shape
    rhs = eqn.invars[1].aval.shape
    return 2.0 * math.prod(out) * math.prod(rhs[:-1])


def matmul_flops(jaxpr) -> float:
    """FLOPs of the dot_generals and convolutions in ``jaxpr``."""
    total = 0.0
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim == "scan":
            total += eqn.params["length"] * matmul_flops(eqn.params["jaxpr"].jaxpr)
            continue
        if prim == "cond":
            total += max(matmul_flops(b.jaxpr) for b in eqn.params["branches"])
            continue
        if prim == "while":
            raise ValueError("a while loop has no static trip count to count")
        if prim == "dot_general":
            total += _dot_flops(eqn)
            continue
        if prim == "conv_general_dilated":
            total += _conv_flops(eqn)
            continue
        for name in CALL_PARAMS:
            sub = eqn.params.get(name)
            if sub is not None:
                total += matmul_flops(getattr(sub, "jaxpr", sub))
                break
    return total


def train_step_flops(loss_fn, params, batch) -> float:
    """Model FLOPs of one forward+backward of ``loss_fn(params, batch)``:
    the forward's matmul FLOPs x 3. Arguments may be ShapeDtypeStructs."""
    closed = jax.make_jaxpr(loss_fn)(params, batch)
    return 3.0 * matmul_flops(closed.jaxpr)
