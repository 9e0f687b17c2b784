"""exchange.kernel_roofline: the exchange kernels' share of their HBM
roofline, in %: the least time their calls could take (the bytes each call
must move, its operands read once and its results written once, from the
shapes in the trace, over the chip's HBM bandwidth) over the device time
the calls took. The kernels are the ``tpu_custom_call`` instructions that
read or write an int8 payload of (blocks, 8, 128) slabs: quantize and
dequant-accumulate of ``kernels/tdm_compress``. Both move far more bytes
than they compute on, so HBM bounds them."""

import re

from bench import trace

_SLAB = re.compile(r"\bs8\[\d+,8,128\]")


def exchange_kernels(ops):
    return [o for o in ops
            if 'custom_call_target="tpu_custom_call"' in o.text and _SLAB.search(o.text)]


def read(ctx):
    lo, hi = ctx["lo"], ctx["hi"]
    least = took = 0.0
    for ops in ctx["trace"].chips.values():
        for o in exchange_kernels(trace.leaf_ops(ops, lo, hi)):
            operands, result = trace.operand_and_result_bytes(o)
            least += (operands + result) / ctx["peaks"]["hbm_bytes_per_s"]
            took += (o.end_ns - o.start_ns) * 1e-9
    if took == 0.0:
        return None
    return 100.0 * least / took
