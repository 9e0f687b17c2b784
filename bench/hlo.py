"""Bytes that a compiled program's collective-permutes put on the links,
read from the optimized HLO text (``compiled.as_text()``)."""

from __future__ import annotations

import math
import re

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}
# the result type of a permute: `%x = TYPE collective-permute[-start](`; a
# start returns a tuple whose first element is the operand being sent
_PERMUTE = re.compile(
    r"=\s*\(?\s*(?P<dtype>[a-z0-9]+)\[(?P<dims>[0-9,]*)\][^=]*?"
    r"\scollective-permute(?:-start)?\("
)
_PAIRS = re.compile(r"source_target_pairs=\{(?P<pairs>(?:\{\d+,\d+\},?)*)\}")


def permute_bytes(hlo_text: str) -> int:
    """Sum over collective-permutes of (operand bytes x source-target
    pairs): what one execution sends over all links, all devices together.
    ``-done`` halves of async permutes send nothing of their own."""
    total = 0
    for line in hlo_text.splitlines():
        m = _PERMUTE.search(line)
        if m is None:
            continue
        pairs = _PAIRS.search(line)
        if pairs is None:
            raise ValueError(f"collective-permute without pairs: {line[:200]}")
        n_pairs = pairs.group("pairs").count("{")
        dims = [int(d) for d in m.group("dims").split(",") if d]
        total += _DTYPE_BYTES[m.group("dtype")] * math.prod(dims) * n_pairs
    return total
