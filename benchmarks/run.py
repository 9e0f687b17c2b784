"""Benchmark harness entry point: one benchmark per paper table/figure plus
the framework's own perf tables.

  fig3        paper Fig. 3 — get1meas vs getMeas clique scaling (wall time)
  constellation  geometry-driven contact plans: round time / ISL bytes sweep
  optimizer   greedy vs rate-aware TDM schedules (never-worse by oracle)
  gossip      paper P2 quantified — consensus speed per TDM topology
  moe         MoE dispatch useful-FLOPs vs capacity factor
  tdm         collective bytes/ops of the TDM primitives (subprocess: 8 devs)
  fused       fused vs per-leaf exchange engine: M vs L×M collectives + wall
              time (subprocess: 8 devs)
  groundseg   ground-segment FL: centralized/hierarchical sink rounds vs
              gossip — cost oracle + measured exchange (subprocess: 8 devs)
  pipeline    pipelined multi-window groundseg rounds: depth x window x
              staleness throughput sweep + HLO-checked measured window
              (subprocess: 8 devs)
  plan_synthesis  mega-constellation plan synthesis: vectorized geometry /
              visibility / windows / routing-DP pipeline vs the retained
              legacy oracles (wall time + speedups)
  serving     constellation serving: TDM-slotted inference end-to-end —
              ground-station ingress, contact-graph routing, replica decode,
              downlink; deterministic sweep + churn + measured decode
              (subprocess: 8 devs)
  roofline    the 40-cell dry-run roofline table (reads experiments/dryrun)

Every benchmark runs as its own ``python -m benchmarks.<module>`` process
and this parent never imports JAX: an accelerator belongs to one process at
a time, so a parent holding it would lock its children out.

``python -m benchmarks.run``            runs everything quick
``python -m benchmarks.run --only fig3 --full``

``--out-dir DIR`` writes one machine-readable ``BENCH_<name>.json`` per
benchmark: ``{"bench": name, "rows": [...], "telemetry": {...}}`` where
``rows`` are the benchmark's ``BENCH {json}`` lines and ``telemetry`` the
flight-recorder counters of the run (``check_regression.py`` accepts the
files, or the whole directory, as ``--run``).
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys


def _banner(name: str):
    print(f"\n{'='*72}\n== {name}\n{'='*72}", flush=True)


def _parse_lines(lines):
    """Pull ``BENCH {json}`` rows and ``TELEMETRY {json}`` counters out of a
    benchmark's output lines."""
    rows, counters = [], {}
    for line in lines:
        if line.startswith("BENCH "):
            try:
                rows.append(json.loads(line[len("BENCH "):]))
            except json.JSONDecodeError:
                pass
        elif line.startswith("TELEMETRY "):
            try:
                counters.update(json.loads(line[len("TELEMETRY "):]))
            except json.JSONDecodeError:
                pass
    return rows, counters


def _write_summary(out_dir, name, rows, counters):
    if out_dir is None:
        return
    out = pathlib.Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    path = out / f"BENCH_{name}.json"
    path.write_text(json.dumps(
        {"bench": name, "rows": rows, "telemetry": counters}, indent=1
    ))
    print(f"wrote {path} ({len(rows)} rows, "
          f"{len(counters)} telemetry counters)", flush=True)


def _subprocess_bench(module: str, extra_args=(), timeout: int = 1200,
                      name: str = None, out_dir=None):
    """Run a benchmark module in its own process (the only way a benchmark
    runs: each owns its device, and some force their own XLA device count,
    which locks at first jax init)."""
    root = pathlib.Path(__file__).resolve().parents[1]
    proc = subprocess.run(
        [sys.executable, "-m", module, *extra_args],
        cwd=root,
        env={**os.environ, "PYTHONPATH": f"{root/'src'}:{root}"},
        capture_output=True, text=True, timeout=timeout,
    )
    print(proc.stdout)
    if proc.returncode != 0:
        print(proc.stderr)
        raise SystemExit(f"{module} failed")
    rows, counters = _parse_lines(proc.stdout.splitlines())
    _write_summary(out_dir, name or module.rsplit(".", 1)[-1], rows, counters)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--only", default=None)
    p.add_argument("--full", action="store_true", help="paper-size sweeps")
    p.add_argument(
        "--out-dir", default=None,
        help="write one BENCH_<name>.json (rows + telemetry counters) per "
             "benchmark into this directory",
    )
    args = p.parse_args(argv)
    want = lambda n: args.only is None or args.only == n
    out_dir = args.out_dir
    full = ["--full"] if args.full else []
    full_or_smoke = full or ["--smoke"]

    if want("fig3"):
        _banner("fig3: paper Fig.3 — TDM primitive scaling over a clique")
        _subprocess_bench("benchmarks.fig3_tdm_scaling", full, name="fig3",
                          out_dir=out_dir)

    if want("constellation"):
        _banner("constellation: geometry-driven round time / ISL traffic sweep")
        _subprocess_bench("benchmarks.constellation_round_time", full,
                          name="constellation", out_dir=out_dir)

    if want("optimizer"):
        _banner("optimizer: greedy vs rate-aware TDM schedules")
        _subprocess_bench("benchmarks.schedule_optimizer", full,
                          name="optimizer", out_dir=out_dir)

    if want("gossip"):
        _banner("gossip: consensus speed per TDM topology (paper P2)")
        _subprocess_bench("benchmarks.gossip_convergence", name="gossip",
                          out_dir=out_dir)

    if want("moe"):
        _banner("moe: dispatch useful-FLOPs vs capacity factor")
        _subprocess_bench("benchmarks.moe_dispatch", name="moe", out_dir=out_dir)

    if want("tdm"):
        _banner("tdm: collective bytes of get1meas / getMeas / int8 (8 devices)")
        _subprocess_bench("benchmarks.tdm_collectives", name="tdm",
                          out_dir=out_dir)

    if want("fused"):
        _banner("fused: flat-buffer exchange engine vs per-leaf (8 devices)")
        _subprocess_bench("benchmarks.fused_exchange", full_or_smoke,
                          timeout=3600, name="fused", out_dir=out_dir)

    if want("groundseg"):
        _banner("groundseg: sink-based FL vs gossip over the same schedule")
        _subprocess_bench("benchmarks.groundseg_round_time", full_or_smoke,
                          timeout=3600, name="groundseg", out_dir=out_dir)

    if want("pipeline"):
        _banner("pipeline: pipelined multi-window groundseg round throughput")
        _subprocess_bench("benchmarks.groundseg_pipeline", full_or_smoke,
                          timeout=3600, name="pipeline", out_dir=out_dir)

    if want("serving"):
        _banner("serving: TDM-slotted inference over the ground segment")
        _subprocess_bench("benchmarks.serving_throughput", full_or_smoke,
                          timeout=3600, name="serving", out_dir=out_dir)

    if want("plan_synthesis"):
        _banner("plan_synthesis: mega-constellation plan pipeline vs legacy")
        _subprocess_bench("benchmarks.plan_synthesis", full_or_smoke,
                          name="plan_synthesis", out_dir=out_dir)

    if want("roofline"):
        _banner("roofline: 40-cell dry-run table (single-pod 16x16)")
        d = pathlib.Path("experiments/dryrun")
        if (d / "single").exists():
            _subprocess_bench("benchmarks.roofline", ["--mesh", "single"],
                              name="roofline", out_dir=out_dir)
        else:
            print("experiments/dryrun/single missing — run "
                  "`python -m repro.launch.dryrun --mesh single` first")

    print("\nall benchmarks done")


if __name__ == "__main__":
    main()
