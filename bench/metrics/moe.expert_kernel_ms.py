"""moe.expert_kernel_ms: device time of a dropless MoE's held experts, per
round and per chip, in ms: the grouped-matmul kernels of ``models/moe.py``
(megablox ``gmm`` for the forward, the recompute and the input gradient,
``tgmm`` for the weight gradient), which a trace names after their entry
points (``%gmm.84``, ``%tgmm.7``)."""

import re

from bench import trace

_KERNEL = re.compile(r"^t?gmm(\.\d+)?$")


def read(ctx):
    lo, hi = ctx["lo"], ctx["hi"]
    tot = sum(
        o.end_ns - o.start_ns
        for ops in ctx["trace"].chips.values()
        for o in trace.leaf_ops(ops, lo, hi)
        if o.opcode == "custom-call" and _KERNEL.match(o.instr)
    )
    if tot == 0 or not ctx.get("rounds"):
        return None
    return tot * 1e-6 / ctx["rounds"] / len(ctx["trace"].chips)
