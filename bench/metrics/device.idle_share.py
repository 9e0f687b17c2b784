"""device.idle_share: 1 - (union of device-op intervals / traced window),
per chip, averaged over the cell's chips, in %."""

from bench import trace


def read(ctx):
    tr = ctx["trace"]
    if not tr.chips:
        return None
    lo, hi = ctx["lo"], ctx["hi"]
    shares = [1.0 - trace.busy_ns(ops, lo, hi) / (hi - lo) for ops in tr.chips.values()]
    return 100.0 * sum(shares) / len(shares)
