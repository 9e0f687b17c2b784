"""exchange.permute_ms: device time of the collective-permute instructions
(``collective-permute``, ``-start`` and ``-done``) in the traced window, per
round and per chip, in ms."""

from bench import trace


def read(ctx):
    lo, hi = ctx["lo"], ctx["hi"]
    tot = sum(
        o.end_ns - o.start_ns
        for ops in ctx["trace"].chips.values()
        for o in trace.leaf_ops(ops, lo, hi)
        if o.opcode.startswith("collective-permute")
    )
    if tot == 0 or not ctx.get("rounds"):
        return None
    return tot * 1e-6 / ctx["rounds"] / len(ctx["trace"].chips)
