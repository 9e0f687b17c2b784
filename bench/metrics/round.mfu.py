"""round.mfu: model FLOPs of the rounds completed in the traced window (the
local steps' forward x 3, summed over nodes; ``bench/flops.py``) over the
window's length x chips x the chip's bf16 peak, in %."""


def read(ctx):
    flops = ctx.get("flops_per_round")
    if not flops or not ctx.get("rounds"):
        return None
    window_s = (ctx["hi"] - ctx["lo"]) * 1e-9
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * ctx["rounds"] * flops / (window_s * peak)
