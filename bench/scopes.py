"""Device time per named scope of the FL round: each instruction of the
round's compiled module (``ctx["hlo_text"]``, ``compiled.as_text()``) is
mapped to the innermost of the program's scopes in its ``op_name``, and the
device time of the trace's leaf ops is summed per scope over the window.

The round program opens these ``jax.named_scope``s (``launch/fl_train.py``,
``core/fused.py``, ``core/tdm.py``): ``local_step`` (forward, loss and
backward), ``optimizer`` (clipping and the AdamW update) and ``exchange``,
inside which ``pack``, ``quantize`` or ``topk``, ``permute``,
``dequant_acc`` or ``scatter_acc``, ``mix`` and ``unpack``. A fusion carries
its root's ``op_name``. Instructions in nested computations (the layer
scan's ``while`` bodies, the ``shard_map`` call) are in the same text, and
instruction names are unique in a module, so the trace's op names index
the map directly. What the compiler made without an ``op_name`` is mapped
as :func:`instruction_scopes` says. Containers (``while``, ``call``,
``conditional``) are left out, as in every sum over ops (``bench/trace.py``).

The module text must be compiled from the program that ran, with its own
metadata: JAX's persistent compile cache leaves metadata out of its key,
so an executable cached from a build without the scopes is loaded, and
reports, without them. A run whose ``ctx`` holds no ``hlo_text`` reads
nothing.
"""

from __future__ import annotations

import collections
import re
from typing import Dict, Optional, Tuple

from bench import trace

SCOPES = (
    "local_step", "optimizer", "exchange", "pack", "quantize", "topk",
    "permute", "dequant_acc", "scatter_acc", "mix", "unpack",
)
UNSCOPED = "unscoped"

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%(?P<name>[^\s(]+)\s.*\{\s*$")
_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%(?P<name>[^\s=]+)\s*=\s*(?:\(.*?\)|\S+)\s+"
    r"(?P<op>[a-z][a-z0-9-]*)\("
)
_OP_NAME = re.compile(r'op_name="(?P<op_name>[^"]*)"')
_CALLED = re.compile(r"\b(?P<kind>calls|body|condition|to_apply)=%(?P<comp>[\w.\-]+)")
_CALLED_SET = re.compile(r"\b(?:branch|called)_computations=\{(?P<comps>[^}]*)\}")
_OPERAND = re.compile(r"%(?P<name>[\w.\-]+)")
_MAX_DEPTH = 64  # links followed from an instruction to a scoped one
# a transformed name-stack segment, e.g. ``transpose(jvp(local_step))``
_WRAPPED = re.compile(r"^(?:[\w.\-]+\()*(?P<bare>[^()]*)\)*$")

Scope = Tuple[Optional[str], bool]


def parse_op_name(op_name: str) -> Scope:
    """(innermost known scope or None, whether the op belongs to a
    backward pass: some name-stack segment is a ``transpose(...)``)."""
    backward = "transpose(" in op_name
    for alternative in op_name.split(";"):
        found = [m.group("bare") for seg in alternative.split("/")
                 if (m := _WRAPPED.match(seg)) and m.group("bare") in SCOPES]
        if found:
            return found[-1], backward
    return None, backward


def instruction_scopes(hlo_text: str) -> Dict[str, Scope]:
    """Instruction name -> (scope or None, backward) for every instruction
    of the module text.

    An instruction whose ``op_name`` names no scope (the compiler made it:
    a layout copy, a prefetch, a cast hoisted out of the layer scan) takes,
    in this order: if it is a fusion, the scope most of its fused
    instructions carry; the scope of the instruction that calls its
    computation (a ``while`` of the layer scan, say); the one scope of all
    the instructions that use its result; the one scope of those whose
    results it reads. What is left has no scope."""
    own: Dict[str, Scope] = {}
    comp_of: Dict[str, str] = {}
    body: Dict[str, list] = collections.defaultdict(list)
    users: Dict[str, list] = collections.defaultdict(list)
    operands: Dict[str, list] = {}
    fused: Dict[str, str] = {}
    caller: Dict[str, str] = {}
    comp = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group("name")
            continue
        m = _INSTR.match(line)
        if m is None:
            continue
        name = m.group("name")
        on = _OP_NAME.search(line)
        own[name] = parse_op_name(on.group("op_name")) if on else (None, False)
        comp_of[name] = comp
        body[comp].append(name)
        operands[name] = _OPERAND.findall(_operands(line[m.end():]))
        for operand in operands[name]:
            users[operand].append(name)
        for c in _CALLED.finditer(line):
            if c.group("kind") == "calls" and m.group("op") == "fusion":
                fused[name] = c.group("comp")
            caller.setdefault(c.group("comp"), name)
        for c in _CALLED_SET.finditer(line):
            for callee in c.group("comps").split(","):
                caller.setdefault(callee.strip().lstrip("%"), name)

    out: Dict[str, Scope] = {}

    def one_scope(names, depth: int) -> Optional[Scope]:
        found = [f for f in (resolve(n, depth + 1) for n in names) if f[0]]
        return found[0] if found and len({f[0] for f in found}) == 1 else None

    def resolve(name: str, depth: int = 0) -> Scope:
        if name in out:
            return out[name]
        scope = own[name]
        if scope[0] is None and name in fused:
            inner = collections.Counter(
                own[i] for i in body.get(fused[name], ()) if own[i][0])
            if inner:
                scope = inner.most_common(1)[0][0]
        if scope[0] is None and depth < _MAX_DEPTH:
            out[name] = scope  # what a walk that comes back here finds
            up = caller.get(comp_of[name])
            if up is not None:
                scope = resolve(up, depth + 1)
            if scope[0] is None:
                scope = one_scope(users.get(name, ()), depth) or scope
            if scope[0] is None:
                scope = one_scope(
                    [o for o in operands[name] if o in own], depth) or scope
        out[name] = scope
        return scope

    for name in own:
        resolve(name)
    return out


def _operands(rest: str) -> str:
    """The operand list at the start of ``rest`` (what follows ``op(``)."""
    depth = 1
    for i, ch in enumerate(rest):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            return rest[:i]
    return rest


def device_ms(ctx, split_backward: bool = False) -> Dict[str, float]:
    """Device time of the window's leaf ops per scope (``UNSCOPED`` for ops
    with none), in ms per round per chip. With ``split_backward`` each
    scope's backward-pass ops count under ``<scope>.backward``."""
    scopes = instruction_scopes(ctx["hlo_text"])
    lo, hi = ctx["lo"], ctx["hi"]
    tot: Dict[str, float] = collections.defaultdict(float)
    for ops in ctx["trace"].chips.values():
        for o in trace.leaf_ops(ops, lo, hi):
            scope, backward = scopes.get(o.instr, (None, False))
            key = scope or UNSCOPED
            if split_backward and backward and scope:
                key += ".backward"
            tot[key] += o.end_ns - o.start_ns
    per = ctx["rounds"] * len(ctx["trace"].chips)
    return {k: v * 1e-6 / per for k, v in tot.items()}


def read_scope(ctx, scope: str) -> Optional[float]:
    """One scope's ms per round per chip; None where the window holds no
    op of it, or the run has no module text or no rounds."""
    if not ctx.get("hlo_text") or not ctx.get("rounds") or not ctx["trace"].chips:
        return None
    return device_ms(ctx).get(scope)
