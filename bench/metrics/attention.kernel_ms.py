"""attention.kernel_ms: device time of the training attention's fused
kernels, per round and per chip, in ms: the Pallas splash-attention
kernels that ``models/attention.py`` runs on a TPU (the forward with its
residuals, and the backward's ``dq`` and ``dkv`` passes), which a trace
names after their entry points (``%splash_mha_fwd_residuals.2``,
``%splash_mha_dq_no_residuals.1``, ``%splash_mha_dkv_no_residuals.1``).
A program whose attention runs the blocked pure-JAX path reads nothing."""

import re

from bench import trace

_KERNEL = re.compile(r"^splash_mq?ha_(fwd|dq|dkv)\w*(\.\d+)?$")


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
