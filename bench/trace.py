"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: per chip, the intervals in which an operation ran, the idle
gaps between them named by what the host was doing, and the device time of
chosen operations.

Planes ``/device:TPU:<n>`` hold one chip each; their ``XLA Ops`` line holds
one event per executed HLO instruction, named by the instruction's text
(``%fusion.12 = bf16[...] fusion(...), ...``). Container instructions
(``while``, ``conditional``, ``call``) enclose the events of their bodies,
so a union of intervals counts each moment once, while a sum over events
leaves containers out. Host spans are the benchmark's own
``jax.profiler.TraceAnnotation`` events, named ``bench.<what>``, on the
host plane. Host and device timestamps share the trace's clock to within
about 0.1 ms.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

HOST_PREFIX = "bench."
CONTAINERS = ("while", "conditional", "call")
_INSTR = re.compile(r"^%(?P<name>[^\s=]+)\s*=\s*(?P<type>\(.*?\)|\S+)\s+(?P<op>[a-z][a-z0-9-]*)\(")

Interval = Tuple[float, float]


@dataclasses.dataclass
class Op:
    start_ns: float
    end_ns: float
    text: str

    @property
    def instr(self) -> str:
        """Instruction name, e.g. ``fusion.12``."""
        m = _INSTR.match(self.text)
        return m.group("name") if m else self.text.split(" ")[0].lstrip("%")

    @property
    def opcode(self) -> str:
        m = _INSTR.match(self.text)
        return m.group("op") if m else ""

    @property
    def result_type(self) -> str:
        m = _INSTR.match(self.text)
        return m.group("type") if m else ""


@dataclasses.dataclass
class Trace:
    chips: Dict[str, List[Op]]          # plane name -> ops, by start
    host: List[Tuple[float, float, str]]  # bench.* spans (start, end, name)

    def span(self, name: str) -> Optional[Interval]:
        """First and last moment of the host spans called ``name``."""
        hits = [(s, e) for s, e, n in self.host if n == name]
        if not hits:
            return None
        return min(s for s, _ in hits), max(e for _, e in hits)


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, got {files}")
    return files[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    chips: Dict[str, List[Op]] = {}
    host: List[Tuple[float, float, str]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = [
                Op(e.start_ns, e.start_ns + e.duration_ns, e.name)
                for line in plane.lines
                if line.name == "XLA Ops"
                for e in line.events
            ]
            if ops:
                chips[plane.name] = sorted(ops, key=lambda o: o.start_ns)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
    return Trace(chips=dict(sorted(chips.items())), host=sorted(host))


def union(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """Disjoint union of ``intervals`` clipped to ``[lo, hi]``, in order."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def busy_ns(ops: Sequence[Op], lo: float, hi: float) -> float:
    return sum(e - s for s, e in union([(o.start_ns, o.end_ns) for o in ops], lo, hi))


def gaps(ops: Sequence[Op], lo: float, hi: float) -> List[Interval]:
    """Idle intervals of one chip inside ``[lo, hi]``."""
    out, t = [], lo
    for s, e in union([(o.start_ns, o.end_ns) for o in ops], lo, hi):
        if s > t:
            out.append((t, s))
        t = e
    if hi > t:
        out.append((t, hi))
    return out


def host_activity(trace: Trace, t: float) -> str:
    """Innermost (shortest) benchmark host span covering ``t``."""
    cover = [(e - s, n) for s, e, n in trace.host if s <= t <= e]
    return min(cover)[1] if cover else "no bench span"


def leaf_ops(ops: Sequence[Op], lo: float, hi: float) -> List[Op]:
    """Ops inside ``[lo, hi]`` that are not containers of other ops."""
    return [
        o for o in ops
        if o.opcode not in CONTAINERS and o.start_ns >= lo and o.end_ns <= hi
    ]


def top_ops(trace: Trace, lo: float, hi: float, n: int = 10) -> List[list]:
    """The ``n`` instructions that took most device time, averaged over the
    chips: ``[["<instr> <result type>", seconds], ...]``."""
    tot: Dict[str, float] = {}
    for ops in trace.chips.values():
        for o in leaf_ops(ops, lo, hi):
            key = f"{o.instr} {re.sub(r'{[^{}]*}', '', o.result_type)}"[:120]
            tot[key] = tot.get(key, 0.0) + (o.end_ns - o.start_ns) * 1e-9
    k = max(len(trace.chips), 1)
    return [[name, s / k] for name, s in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def top_gaps(trace: Trace, lo: float, hi: float, n: int = 10) -> List[list]:
    """The ``n`` longest idle gaps over all chips, each named by the chip
    and what the host was doing in its middle."""
    out = []
    for chip, ops in trace.chips.items():
        for s, e in gaps(ops, lo, hi):
            name = f"{host_activity(trace, (s + e) / 2)} ({chip.rsplit('/', 1)[-1]})"
            out.append([name, (e - s) * 1e-9])
    return sorted(out, key=lambda g: -g[1])[:n]


# ---------------------------------------------------------------------------
# shapes in instruction text
# ---------------------------------------------------------------------------

_SHAPE = re.compile(
    r"\b(?P<dtype>pred|[sufc]\d+|bf16|f8e4m3fn|f8e5m2)\[(?P<dims>[0-9,]*)\](?P<layout>\{[^}]*\})?"
)
_DTYPE_BYTES = {"pred": 1, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1}


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    size = _DTYPE_BYTES.get(dtype)
    if size is None:
        size = int(dtype[1:]) // 8
    return n * size


def _hbm_bytes(text: str) -> int:
    """Bytes of the shapes in ``text`` that live in HBM: a layout with a
    memory-space mark ``S(n)`` is in on-chip memory, filled by a copy of
    its own."""
    return sum(_shape_bytes(d, s) for d, s, layout in _SHAPE.findall(text)
               if "S(" not in layout)


def operand_and_result_bytes(op: Op) -> Tuple[int, int]:
    """HBM bytes of the instruction's operands and of its result, from the
    shapes written in its text (tiling ignored)."""
    m = _INSTR.match(op.text)
    if m is None:
        raise ValueError(f"not an instruction: {op.text[:120]}")
    result = _hbm_bytes(m.group("type"))
    args = op.text[m.end():]
    depth, end = 1, 0
    for i, ch in enumerate(args):
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0:
            end = i
            break
    operands = _hbm_bytes(args[:end])
    return operands, result
