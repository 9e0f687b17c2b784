"""CPU checks of the per-scope reduction (``bench/scopes.py``) and of its
readers, on a synthetic trace and module text, and of the accepted
readers on the recorded v5e trace.

    PYTHONPATH=src python -m pytest -q bench/tests/test_scopes.py
"""

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, peaks, scopes, trace  # noqa: E402


def _reader(name):
    return harness.load_module(ROOT / "bench" / "metrics" / f"{name}.py", name).read


def test_recorded_trace_reads_mfu_and_idle_as_before():
    """The accepted readers read the recorded trace to the bit as the
    benchmark's first version did (values taken from it)."""
    tr = trace.load(str(ROOT / "bench" / "testdata" / "small.xplane.pb"))
    ops = tr.chips["/device:TPU:0"]
    lo, hi = min(o.start_ns for o in ops), max(o.end_ns for o in ops)
    ctx = {"trace": tr, "lo": lo, "hi": hi, "rounds": 3, "chips": 1,
           "flops_per_round": 2 * 1024**3, "peaks": peaks.PEAKS["TPU v5 lite"]}
    assert (lo, hi) == (45256389.0, 46061519.0)
    assert _reader("round.mfu")(ctx) == 4.061803270285639
    assert _reader("device.idle_share")(ctx) == 94.59304708556382


# a round's module: the layer scan's while body holding a copy the compiler
# made (no op_name: it takes the while's scope), a fusion whose root the
# compiler made (it takes its fused instructions' scope), a named kernel, a
# permute, and a copy with no scope anywhere
SCOPED_HLO = """\
HloModule jit_node_round

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %multiply.1 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(node_round)/shard_map/optimizer/mul"}
  ROOT %convert.1 = f32[8]{0} convert(%multiply.1)
}

%body.1 (p.1: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p.1 = (s32[], f32[8]{0}) parameter(0)
  %fusion.2 = f32[8]{0} fusion(%p.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(node_round)/shard_map/local_step/transpose(jvp())/while/body/mul"}
  %fusion.3 = f32[8]{0} fusion(%fusion.2), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(node_round)/shard_map/local_step/jvp()/while/body/add"}
  ROOT %copy.2 = f32[8]{0} copy(%fusion.3)
}

ENTRY %main.9 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %while.1 = (s32[], f32[8]{0}) while(%x), condition=%cond.1, body=%body.1, metadata={op_name="jit(node_round)/shard_map/local_step/jvp()/while"}
  %fusion.1 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1
  %tdm_quantize.1 = (s8[1,8,128]{2,1,0}, f32[1,1]{1,0}) custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(node_round)/shard_map/exchange/quantize/tdm_quantize/pallas_call"}
  %collective-permute.1 = f32[8]{0} collective-permute(%x), channel_id=1, source_target_pairs={{0,1},{1,0}}, metadata={op_name="jit(node_round)/shard_map/exchange/permute/ppermute"}
  ROOT %copy.1 = f32[8]{0} copy(%x)
}
"""


def _scoped_trace():
    """Two chips, two rounds: each chip runs, per round, backward 20 ns,
    forward 10 + 5, optimizer 10, quantize 10, permute 10, unscoped 5, all
    inside a while container that is left out."""
    texts = {
        "while.1": "%while.1 = (s32[], f32[8]{0}) while(%x)",
        "fusion.2": "%fusion.2 = f32[8]{0} fusion(%p.1)",
        "fusion.3": "%fusion.3 = f32[8]{0} fusion(%fusion.2)",
        "copy.2": "%copy.2 = f32[8]{0} copy(%fusion.3)",
        "fusion.1": "%fusion.1 = f32[8]{0} fusion(%x)",
        "tdm_quantize.1": "%tdm_quantize.1 = (s8[1,8,128]{2,1,0}, f32[1,1]{1,0}) custom-call(%x)",
        "collective-permute.1": "%collective-permute.1 = f32[8]{0} collective-permute(%x)",
        "copy.1": "%copy.1 = f32[8]{0} copy(%x)",
    }
    chips = {}
    for c in range(2):
        ops = []
        for r in range(2):
            t = 1000 * r + 7 * c
            ops.append(trace.Op(t, t + 100, texts["while.1"]))
            for name, dur in (("fusion.2", 20), ("fusion.3", 10), ("copy.2", 5),
                              ("fusion.1", 10), ("tdm_quantize.1", 10),
                              ("collective-permute.1", 10), ("copy.1", 5)):
                ops.append(trace.Op(t, t + dur, texts[name]))
                t += dur
        chips[f"/device:TPU:{c}"] = ops
    return trace.Trace(chips=chips, host=[])


def test_scopes_attribute_device_time_by_op_name():
    ctx = {"trace": _scoped_trace(), "lo": 0, "hi": 5000, "rounds": 2,
           "hlo_text": SCOPED_HLO}
    want = {"local_step": 35e-6, "optimizer": 10e-6, "quantize": 10e-6,
            "permute": 10e-6, scopes.UNSCOPED: 5e-6}
    assert scopes.device_ms(ctx) == pytest.approx(want, rel=1e-12, abs=0)
    split = scopes.device_ms(ctx, split_backward=True)
    assert split["local_step"] == pytest.approx(15e-6)
    assert split["local_step.backward"] == pytest.approx(20e-6)
    assert _reader("round.local_step_ms")(ctx) == pytest.approx(35e-6)
    assert _reader("round.optimizer_ms")(ctx) == pytest.approx(10e-6)
    # a program without the scopes, or a run without the module text,
    # reads nothing and raises nothing
    bare = SCOPED_HLO.replace("local_step/", "").replace("optimizer/", "")
    assert _reader("round.local_step_ms")(dict(ctx, hlo_text=bare)) is None
    assert _reader("round.optimizer_ms")(dict(ctx, hlo_text=None)) is None


@pytest.mark.parametrize("op_name,want", [
    ("jit(node_round)/shard_map/local_step/jvp()/while/body/dot_general", ("local_step", False)),
    ("jit(node_round)/shard_map/local_step/transpose(jvp())/while/body/mul", ("local_step", True)),
    ("jit(node_round)/shard_map/exchange/mix/permute/ppermute", ("permute", False)),
    ("jit(f)/transpose(jvp(optimizer))/mul", ("optimizer", True)),
    ("jit(node_round)/shard_map/broadcast_in_dim", (None, False)),
])
def test_scope_of_op_name_is_innermost_known(op_name, want):
    assert scopes.parse_op_name(op_name) == want
