"""One run of one cell: set-up, the measured window (or the traced one),
the reference check, and the result line.

Everything that belongs to one configuration, traffic mix, cell or metric
is a file of its own, found by the name ``BENCHMARK.json`` gives:

- ``bench/configs/<config>.json``  the configuration as it is run, and
  ``bench/configs/<config>.py``    its plain reference (weights and loss);
- ``bench/traffic/<traffic>.json`` the traffic mix, which names its runner
  ``bench/runners/<runner>.py``;
- ``bench/limits/<cell>.json``     the limits of the numbers ``correct`` is
  decided on, with the readings they were set from;
- ``bench/metrics/<metric>.py``    one reader per per-layer metric.
"""

from __future__ import annotations

import importlib.util
import json
import math
import pathlib
import shutil
import tempfile
import time

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent


def load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.exists():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_config(name: str):
    conf = json.loads((HERE / "configs" / f"{name}.json").read_text())
    model = load_module(HERE / "configs" / f"{name}.py", f"bench_ref_{name}")
    return conf, model


def load_limits(cell: str) -> dict:
    return json.loads((HERE / "limits" / f"{cell}.json").read_text())["limits"]


def metric_readers(bench: dict, cell: str) -> dict:
    """Per-layer metrics that this cell reports, name -> reader."""
    out = {}
    for m in bench["per_layer"]:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        out[m["name"]] = (m, load_module(HERE / "metrics" / f"{m['name']}.py",
                                         f"bench_metric_{m['name']}"))
    return out


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache."""

    def __init__(self):
        import jax

        self.n = 0

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.n += 1

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.n += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def device_info(devices, chips_used) -> dict:
    peaks = [d.memory_stats().get("peak_bytes_in_use", 0) if d.memory_stats() else 0
             for d in chips_used]
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(max(peaks)),
    }


def run_cell(bench: dict, cell: dict, seed: int, seconds: float, trace: bool,
             devices, t0: float, log=print, *, conf=None, model=None, mix=None,
             limits=None, program_fault=None) -> dict:
    """One run of ``cell``; returns the result object (the last stdout
    line). The configuration, its reference module, the traffic mix and
    the limits are found by name unless given; ``program_fault`` lets a
    test break the timed path under the harness."""
    import jax

    from bench import reference, traffic
    from bench import trace as trace_lib

    if conf is None:
        conf, model = load_config(cell["config"])
    mix = mix or traffic.load(cell["traffic"])
    limits = limits or load_limits(cell["name"])
    runner = load_module(HERE / "runners" / f"{mix['runner']}.py", f"bench_runner_{mix['runner']}")
    counter = CompileCounter()

    c = runner.FLCell(model, conf, mix, seed)
    if program_fault is not None:
        program_fault(c)
    prog = c.setup()
    log(f"set-up through {runner.FIRST_ROUNDS} rounds: {time.perf_counter() - t0:.1f} s")
    result = {"correct": False, "attempted": 0, "failed": 0, "metrics": {}}
    breakdown = None
    ctx = {}
    if trace:
        compiled = c.compiled_round()
        from bench import hlo

        ctx["isl_bytes_per_round"] = hlo.permute_bytes(compiled.as_text())
        mem = compiled.memory_analysis()
        log(f"round program: arguments {mem.argument_size_in_bytes / 2**30:.2f} GiB, "
            f"temporaries {mem.temp_size_in_bytes / 2**30:.2f} GiB")
        ctx["flops_per_round"] = c.round_flops()
        del compiled
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(tdir, profiler_options=opts)
    setup_s = time.perf_counter() - t0
    compiles = counter.n
    rounds, wall = c.window(min(seconds, mix["trace_seconds"]) if trace else seconds)
    compiles = counter.n - compiles
    losses = np.asarray(jax.numpy.stack(c.cache.losses[runner.FIRST_ROUNDS:]))
    result["attempted"] = int(rounds)
    result["failed"] = int(np.sum(~np.all(np.isfinite(losses), axis=1)))
    log(f"window: {rounds} rounds in {wall:.3f} s, {compiles} compiles inside it")
    if trace:
        jax.profiler.stop_trace()
    result["device"] = device_info(jax.devices(), devices[: mix["nodes"]])
    if trace:
        tr = trace_lib.load(trace_lib.find_xplane(tdir))
        shutil.rmtree(tdir, ignore_errors=True)
        lo, hi = tr.span("bench.window")
        from bench import peaks

        ctx.update(trace=tr, lo=lo, hi=hi, rounds=rounds, chips=mix["nodes"],
                   peaks=peaks.peaks_for(devices[0].device_kind))
        busy = [trace_lib.busy_ns(ops, lo, hi) for ops in tr.chips.values()]
        result["device"]["busy_s"] = float(np.mean(busy)) * 1e-9 if busy else 0.0
        result["device"]["window_s"] = (hi - lo) * 1e-9
        for name, (spec, reader) in metric_readers(bench, cell["name"]).items():
            value = reader.read(ctx)
            if value is not None:
                result["metrics"][name] = {"value": value, "unit": spec["unit"]}
        breakdown = {
            "device_ops": trace_lib.top_ops(tr, lo, hi),
            "idle_gaps": trace_lib.top_gaps(tr, lo, hi),
        }
    else:
        result["metrics"] = {
            "setup_s": {"value": setup_s, "unit": "s"},
            "round_s": {"value": wall / rounds, "unit": "s"},
        }

    # the reference, once the window has closed and the program's state is freed
    key = c.key
    batches = c.warm_batches()
    c.free()
    del c
    in_use = [d.memory_stats().get("bytes_in_use", 0) for d in devices[: mix["nodes"]]
              if d.memory_stats()]
    log(f"before the reference: {max(in_use, default=0) / 2**30:.2f} GiB in use on the fullest chip")
    t_ref = time.perf_counter()
    ref = reference.run(model, conf, mix, batches, key, devices, runner.FIRST_ROUNDS,
                        log=log)
    numbers = reference.compare(prog, ref)
    log(f"reference: {time.perf_counter() - t_ref:.1f} s")
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    finite = all(math.isfinite(v) for v in numbers.values())
    result["correct"] = bool(
        finite and result["failed"] == 0 and rounds > 0
        and all(v["value"] <= v["limit"] for v in checks.values())
    )
    result["compiles_in_window"] = compiles
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def check_lines(result: dict) -> list:
    return [
        f"check {k}: {v['value']!r} limit {v['limit']!r} "
        f"{'ok' if v['value'] <= v['limit'] else 'FAILED'}"
        for k, v in result["checks"].items()
    ] + [f"check failed_rounds: {result['failed']} limit 0"]
