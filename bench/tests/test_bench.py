"""CPU checks of the benchmark's yardstick at small sizes: the HLO byte
count, the FLOP counter, the trace reduction (on a trace recorded on a
v5e), the control, and that a run with the timed path broken reads
``correct: false``.

    PYTHONPATH=src python -m pytest -q bench/tests
"""

import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import calibrate, flops, harness, hlo, peaks, reference, trace, traffic  # noqa: E402

TESTDATA = ROOT / "bench" / "testdata"

# two permutes of the four-chip ring round (HLO of a v5e:2x2 compile)
RING_HLO = """\
  %collective-permute-start.4 = (s8[780149760]{0:T(1024)(128)(4,1)}, s8[780149760]{0:T(1024)(128)(4,1)}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%bitcast.234), channel_id=1, source_target_pairs={{0,1},{1,0},{2,3},{3,2}}, metadata={op_name="jit(node_round)/shard_map/ppermute"}
  %collective-permute-start.3 = (f32[761865]{0:T(1024)S(1)}, f32[761865]{0:T(1024)S(1)}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%reduce.43), channel_id=1, source_target_pairs={{0,3},{3,0}}, metadata={op_name="jit(node_round)/shard_map/ppermute"}
  %collective-permute-done.3 = f32[761865]{0:T(1024)S(1)} collective-permute-done(%collective-permute-start.3), metadata={op_name="jit(node_round)/shard_map/ppermute"}
  %collective-permute.9 = bf16[16,8]{1,0} collective-permute(%x), channel_id=2, source_target_pairs={{0,1},{1,2},{2,0}}
"""


def test_permute_bytes_counts_operand_times_pairs():
    want = 780149760 * 4 + 761865 * 4 * 2 + 16 * 8 * 2 * 3
    assert hlo.permute_bytes(RING_HLO) == want


def test_matmul_flops_count_forward_once_whatever_the_remat():
    w = jax.ShapeDtypeStruct((6, 16, 32), jnp.float32)
    x = jax.ShapeDtypeStruct((4, 16), jnp.float32)

    def loss(w, x, remat):
        body = lambda h, wl: (jnp.tanh(h @ wl)[:, :16], None)
        h, _ = jax.lax.scan(jax.checkpoint(body) if remat else body, x, w)
        return jnp.sum(h)

    plain = flops.train_step_flops(lambda w, x: loss(w, x, False), w, x)
    remat = flops.train_step_flops(lambda w, x: loss(w, x, True), w, x)
    assert plain == remat == 3 * 6 * 2 * 4 * 16 * 32


def test_instruction_bytes_from_its_shapes():
    op = trace.Op(0, 1, '%k.1 = (s8[4,8,128]{2,1,0}, f32[1,4]{1,0}) custom-call(f32[4,8,128]{2,1,0} %x, f32[1,4]{1,0:T(1,128)S(1)} %s), custom_call_target="tpu_custom_call"')
    assert op.instr == "k.1" and op.opcode == "custom-call"
    assert trace.operand_and_result_bytes(op) == (4 * 1024 * 4, 4 * 1024 + 16)


def test_union_and_gaps():
    ops = [trace.Op(0, 10, "a"), trace.Op(5, 20, "b"), trace.Op(30, 40, "c")]
    assert trace.busy_ns(ops, 0, 50) == 30
    assert trace.gaps(ops, 0, 50) == [(20, 30), (40, 50)]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


@pytest.fixture(scope="module")
def recorded():
    path = TESTDATA / "small.xplane.pb"
    tr = trace.load(str(path))
    # this trace's device clock runs ~0.7 ms ahead of its host clock, more
    # than its whole window, so the device extent stands in for the window
    ops = tr.chips["/device:TPU:0"]
    return tr, min(o.start_ns for o in ops), max(o.end_ns for o in ops)


def test_recorded_trace_reduces_to_busy_idle_and_kernels(recorded):
    tr, lo, hi = recorded
    assert list(tr.chips) == ["/device:TPU:0"]
    ops = tr.chips["/device:TPU:0"]
    busy = trace.busy_ns(ops, lo, hi)
    assert 0 < busy < hi - lo
    idle = sum(e - s for s, e in trace.gaps(ops, lo, hi))
    assert abs(busy + idle - (hi - lo)) < 1
    names = {g[0].split(" (")[0] for g in trace.top_gaps(tr, lo, hi)}
    assert names <= {"bench.window", "bench.dispatch", "bench.drain", "no bench span"}
    assert [n for _, _, n in tr.host] == [
        "bench.window", "bench.dispatch", "bench.drain", "bench.dispatch", "bench.drain"]
    reader = harness.load_module(ROOT / "bench" / "metrics" / "exchange.kernel_roofline.py", "kr")
    kernels = reader.exchange_kernels(trace.leaf_ops(ops, lo, hi))
    n, nb = 1 << 20, (1 << 20) // 1024
    got = sorted(trace.operand_and_result_bytes(k) for k in kernels)
    # dequant-accumulate's operands and result sit in on-chip memory
    # (layout mark S(1)) at this size: only its scalar weight is read from HBM
    assert got == sorted([(4 * n, n + 4 * nb), (4, 0)])
    share = reader.read({"trace": tr, "lo": lo, "hi": hi, "peaks": peaks.PEAKS["TPU v5 lite"]})
    assert 0 < share <= 100
    idle_reader = harness.load_module(ROOT / "bench" / "metrics" / "device.idle_share.py", "ir")
    assert 0 < idle_reader.read({"trace": tr, "lo": lo, "hi": hi}) < 100


# (configuration, traffic) of the cell in BENCHMARK.json and of the two
# mixes whose cells are still to be proven on the chip
RING = ("mamba2-780m", "ring4.int8")
SOLO = ("mamba2-780m", "solo")
WHISPER = ("whisper-base", "solo.b64x448")


def _tiny_run(cell, fault="", seed=2**31 + 7, limits=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if cell == RING:
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    cmd = [sys.executable, str(ROOT / "bench" / "tests" / "tiny_run.py"),
           "--config", cell[0], "--traffic", cell[1], "--fault", fault, "--seed", str(seed)]
    if limits:
        cmd += ["--limits", json.dumps(limits)]
    out = subprocess.run(cmd, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


_LIMITS = {}


def tiny_limits(cell):
    """Limits at the tiny size, set from sound runs on seeds of their own
    as the cell's are: three times the larger of two readings. (At this
    size a leaf holds a few dozen elements, and a norm's gap swings several
    times from seed to seed.)"""
    if cell not in _LIMITS:
        unset = {"loss_gap": 1.0, "grad_norm_gap": 1.0, "update_norm_gap": 1.0}
        runs = [_tiny_run(cell, seed=s, limits=unset)["checks"] for s in (11, 13)]
        _LIMITS[cell] = {k: 3 * max(max(r[k]["value"] for r in runs), 1e-7)
                         for k in runs[0]}
    return _LIMITS[cell]


def test_control_in_fp8_reads_above_the_limits():
    """The control (the reference in fp8, in the program's place) fails a
    limit set from sound runs, at a test's size, on three seeds."""
    sys.path.insert(0, str(ROOT / "bench" / "tests"))
    import tiny_run

    conf, model = tiny_run.tiny_config("mamba2-780m")
    mix = tiny_run.tiny_mix({"traffic": "solo"})
    limits = tiny_limits(SOLO)
    for seed in (1, 2, 3):
        batches = traffic.make_pool(mix, conf["vocab_size"], seed)
        key = traffic.seed_words(seed)
        ref = reference.run(model, conf, mix, batches, key, jax.devices(), 3)
        low = reference.run(model, conf, mix, batches, key, jax.devices(), 3,
                            cast=calibrate.fp8_cast())
        numbers = reference.compare(low, ref)
        assert any(v > limits[k] for k, v in numbers.items()), (numbers, limits)


@pytest.mark.parametrize("cell", [SOLO, RING, WHISPER])
def test_sound_tiny_run_is_correct(cell):
    res = _tiny_run(cell, seed=12, limits=tiny_limits(cell))
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("cell,fault", [
    (SOLO, "frozen"), (SOLO, "half_batch"), (WHISPER, "frozen"), (WHISPER, "half_batch"),
    (RING, "frozen"), (RING, "half_batch"), (RING, "no_exchange"),
])
def test_broken_timed_path_reads_incorrect(cell, fault):
    res = _tiny_run(cell, fault, seed=12, limits=tiny_limits(cell))
    assert not res["correct"], res["checks"]
