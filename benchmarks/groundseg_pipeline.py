"""Pipelined ground-segment rounds: depth x window-length x staleness sweep.

The tentpole claim this benchmark trends: at ``pipeline_depth=2`` round
r's downlink flood and round r+1's uplink relay share ONE contact window
on disjoint slot capacity, so the engine completes one round per window
instead of one per two (the one-shot engine traverses the window twice —
uplink, then "the next identical window" for the downlink). Steady-state
round throughput should be >= 1.5x depth 1 on the MEO shell sweep
(2.0x when the leftover capacity still covers every satellite, which it
does on these shells — the ``uncovered`` metric would show otherwise).

Two layers, emitted as ``BENCH {json}`` lines (and optionally ``--out``):

1. **Cost-oracle sweep** (pure Python, deterministic): for each shell x
   window-length (contact-plan steps) x staleness-horizon x depth cell,
   the steady-state throughput model (:func:`repro.constellation.cost.
   groundseg_throughput`), the occupancy oracle
   (:func:`~repro.constellation.cost.groundseg_schedule_cost`) and the
   router's delivery statistics. A delay-tolerance cell kills one
   satellite for the warm-up window and reports the stale delivery age
   once it revives.

2. **Measured exchange** (8 forced host devices): the compiled pipelined
   window (:func:`repro.groundseg.aggregation.pipelined_window_round`) at
   depth 1 vs depth 2, HLO collective counts checked against the extended
   ``expected_collectives`` static oracle (deterministic), wall clock
   advisory.

Run as its own process (device count lock):
  PYTHONPATH=src python -m benchmarks.groundseg_pipeline --smoke
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 " + os.environ.get("XLA_FLAGS", "")
)

import argparse
import json
import pathlib
import time

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro import telemetry
from repro.constellation import cost
from repro.constellation.scenario import ScenarioSpec, ShellSpec, build_scenario
from repro.groundseg import aggregation, routing
from repro.launch.hlo_stats import collective_stats
from repro.telemetry import audit

N_GS = 2   # canonical scenario.GROUND_SITES prefix (equator + midlat-e)

QUICK_SHELLS = [(2, 3)]
DEFAULT_SHELLS = [(2, 3), (2, 4)]
FULL_SHELLS = [(2, 3), (2, 4), (3, 4), (4, 5)]


def build_sched(planes, per_plane, steps, altitude_km, antennas, payload):
    """One scenario-factory deployment; the ground segment is the canonical
    ``scenario.GROUND_SITES`` prefix (this file used to carry its own copy)."""
    scn = build_scenario(ScenarioSpec(
        shells=(ShellSpec(
            planes=planes, per_plane=per_plane, altitude_km=altitude_km,
        ),),
        n_ground=N_GS,
        steps=steps,
        antennas=antennas,
        payload_bytes=payload,
    ))
    sinks = sorted(scn.ground_ids)
    return scn.geom, scn.plan, scn.schedule(), sinks


def oracle_rows(shells, steps_list, staleness_list, payload, antennas,
                altitude):
    rows = []
    for planes, per in shells:
        for steps in steps_list:
            geom, plan, sched, sinks = build_sched(
                planes, per, steps, altitude, antennas, payload
            )
            for stale in staleness_list:
                per_depth = {}
                for depth in (1, 2):
                    th = cost.groundseg_throughput(
                        sched, sinks, n_nodes=plan.n_nodes,
                        pipeline_depth=depth, max_staleness_windows=stale,
                    )
                    occ = cost.groundseg_schedule_cost(
                        sched, sinks, payload, n_nodes=plan.n_nodes,
                        pipeline_depth=depth, max_staleness_windows=stale,
                    )
                    n_sats = geom.total
                    row = dict(
                        bench="groundseg_pipeline",
                        planes=planes, per_plane=per, n_sats=n_sats,
                        n_gs=N_GS, steps=steps,
                        staleness=stale, depth=depth,
                        window_s=th["window_s"],
                        est_occupancy_s=occ.time_s,
                        est_mbytes_isl=occ.bytes_on_isl / 1e6,
                        thpt_rounds_per_ks=th["round_throughput_per_s"] * 1e3,
                        undelivered=float(n_sats - th["delivered"]),
                        uncovered=float(n_sats - th["covered"]),
                        carried=th["carried"],
                        dropped=th["dropped"],
                    )
                    per_depth[depth] = row
                    rows.append(row)
                ratio = (
                    per_depth[2]["thpt_rounds_per_ks"]
                    / max(per_depth[1]["thpt_rounds_per_ks"], 1e-12)
                )
                rows.append(dict(
                    bench="groundseg_pipeline_summary",
                    planes=planes, per_plane=per, steps=steps,
                    staleness=stale,
                    throughput_ratio_d2_over_d1=ratio,
                    # lower-is-better form for the regression trender
                    inv_throughput_ratio=1.0 / max(ratio, 1e-12),
                ))
    return rows


def delay_tolerance_rows(payload, antennas, altitude, steps, staleness):
    """Deterministic delay-tolerance scenario: one satellite is OCCLUDED
    (alive, so it snapshots a payload, but contactless) for the warm-up
    window; once its contacts return the queued payload delivers one
    window stale — the oracle-side twin of the multi-device staleness
    tests."""
    geom, plan, sched, sinks = build_sched(
        2, 3, steps, altitude, antennas, payload
    )
    rels = list(sched.tdm)
    n = plan.n_nodes
    occluded = 0
    others = set(range(n)) - {occluded}
    router = routing.MultiWindowRouter(
        n, sinks, max_staleness_windows=staleness, pipeline_depth=2
    )
    # window 0: the satellite is live (injects its snapshot) but none of
    # its contacts exist — the payload must persist
    wp0 = router.plan_window([r.restrict(others) for r in rels])
    wp1 = router.plan_window(rels)          # contacts back: stale delivery
    # route-provenance audit over the scenario's per-window slot relations
    verdict = audit.audit_window_programs(
        [wp0, wp1], decay=0.5,
        slots=[[r.restrict(others) for r in rels], rels],
    )
    rows = [dict(
        bench="groundseg_delay_tolerance",
        planes=2, per_plane=3, steps=steps, staleness=staleness,
        occluded_sat=occluded,
        warmup_delivered=float(wp0.uplink.delivered_count()),
        warmup_carried=float(len(wp0.residual)),
        steady_delivered=float(wp1.uplink.delivered_count()),
        stale_age=float(wp1.delivered_ages.get(occluded, -1)),
        dropped=float(len(wp1.dropped)),
        audit_violations=float(len(verdict.violations)),
    )]
    return rows, verdict


# ---------------------------------------------------------------------------
# Measured pipelined exchange on the host-device mesh
# ---------------------------------------------------------------------------

def measure(fn, args, reps):
    rec = telemetry.get_recorder()
    with rec.span("bench.compile", cat="compile"):
        compiled = fn.lower(*args).compile()
    stats = collective_stats(compiled.as_text())
    out = compiled(*args)
    jax.block_until_ready(out)
    with rec.span("bench.measure", cat="bench", reps=reps):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = compiled(*args)
        jax.block_until_ready(out)
        wall = (time.perf_counter() - t0) / reps
    rec.counter("bench.measured_cells")
    return stats, wall


def measured_rows(payload_leaves, leaf_elems, antennas, steps, altitude,
                  reps):
    from benchmarks.fused_exchange import make_tree

    rows = []
    geom, plan, sched, sinks = build_sched(
        2, 3, steps, altitude, antennas, 1 << 22
    )
    n = plan.n_nodes
    if n > len(jax.devices()):
        print(f"skipping measured cells: need {n} devices, "
              f"have {len(jax.devices())}")
        return rows
    mesh = Mesh(np.array(jax.devices()[:n]), ("node",))
    rels = list(sched.tdm)
    tree = make_tree(payload_leaves, leaf_elems, n=n)
    from repro.core import fused
    spec = fused.build_spec(
        jax.tree.map(lambda x: x[0], tree)
    )
    carry = aggregation.stacked_zero_buffers(spec, n)
    pend = aggregation.stacked_zero_buffers(spec, n)

    for depth in (1, 2):
        router = routing.MultiWindowRouter(
            n, sinks, max_staleness_windows=2, pipeline_depth=depth
        )
        router.plan_window(rels)
        wp = router.plan_window(rels)   # steady-state window

        def body(t, c, p, wp=wp):
            t = jax.tree.map(lambda x: x[0], t)
            c = jax.tree.map(lambda x: x[0], c)
            p = jax.tree.map(lambda x: x[0], p)
            out, nc, npend = aggregation.pipelined_window_round(
                t, c, p, wp, "node", pool=True, staleness_decay=0.5,
            )
            return tuple(
                jax.tree.map(lambda x: x[None], z) for z in (out, nc, npend)
            )

        fn = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(P("node"),) * 3,
            out_specs=(P("node"),) * 3, check_vma=False,
        ))
        stats, wall = measure(fn, (tree, carry, pend), reps)
        want = aggregation.expected_window_collectives(
            wp, len(spec.buckets), compression="none", pool=True
        )
        got_permutes = stats.count_by_kind.get("collective-permute", 0)
        ok = got_permutes == want["collective-permute"]
        row = dict(
            bench="groundseg_pipeline_measured",
            n_sats=geom.total, n_gs=len(sinks), depth=depth,
            permutes=got_permutes,
            expected_permutes=want["collective-permute"],
            oracle_match=bool(ok),
            collective_bytes=stats.total_bytes,
            wall_ms=wall * 1e3,
        )
        rows.append(row)
        print(
            f"measured depth {depth}: permutes {got_permutes} "
            f"(oracle {want['collective-permute']}, "
            f"{'match' if ok else 'MISMATCH'})  "
            f"coll {stats.total_bytes/2**20:.2f} MB  wall {wall*1e3:.2f} ms"
        )
        print("BENCH " + json.dumps(row), flush=True)
        if not ok:
            raise SystemExit(
                "HLO collective count diverged from the static oracle"
            )
    return rows


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true", help="small sweep")
    p.add_argument("--full", action="store_true", help="larger shells")
    p.add_argument("--antennas", type=int, default=2)
    p.add_argument("--altitude", type=float, default=8062.0)
    p.add_argument("--payload-mib", type=float, default=4.0)
    p.add_argument("--reps", type=int, default=None)
    p.add_argument("--out", default=None, help="write BENCH rows as json")
    p.add_argument("--trace", default=None,
                   help="write a Chrome trace (Perfetto) of this run")
    p.add_argument("--report", default=None, metavar="PREFIX",
                   help="write PREFIX.md/.json mission report of this run")
    args = p.parse_args(argv)
    with telemetry.trace_scope(args.trace):
        rows, verdict = _main(args)
        print("TELEMETRY " + json.dumps(telemetry.counters_snapshot()),
              flush=True)
        if args.report:
            from repro.telemetry.report import write_report

            md, js = write_report(
                args.report,
                audit=verdict,
                title="groundseg pipeline bench",
                extra={
                    "bench": "groundseg_pipeline",
                    "n_rows": len(rows),
                    "args": {
                        "smoke": args.smoke, "full": args.full,
                        "reps": args.reps, "antennas": args.antennas,
                    },
                },
            )
            print(f"wrote mission report to {md} and {js}")
        if not verdict.ok:
            raise SystemExit(
                f"route-provenance audit failed: "
                f"{len(verdict.violations)} violation(s)"
            )
    return rows


def _main(args):
    if args.smoke:
        shells, steps_list, stales, reps = QUICK_SHELLS, [8], [0, 2], 3
        leaves, elems = 8, 1 << 10
    elif args.full:
        shells, steps_list = FULL_SHELLS, [8, 12, 16]
        stales, reps = [0, 1, 2, 4], 10
        leaves, elems = 24, 1 << 12
    else:
        shells, steps_list = DEFAULT_SHELLS, [8, 12]
        stales, reps = [0, 1, 2], 5
        leaves, elems = 12, 1 << 10
    reps = args.reps or reps

    payload = int(args.payload_mib * (1 << 20))
    rows = oracle_rows(shells, steps_list, stales, payload, args.antennas,
                       args.altitude)
    hdr = (f"{'shell':>6} {'steps':>6} {'stale':>6} {'depth':>6} "
           f"{'thpt/ks':>9} {'occup_s':>9} {'undeliv':>8} {'uncov':>6}")
    print(hdr)
    for r in rows:
        if r["bench"] != "groundseg_pipeline":
            continue
        print(
            f"{r['planes']}x{r['per_plane']:<4} {r['steps']:>6} "
            f"{r['staleness']:>6} {r['depth']:>6} "
            f"{r['thpt_rounds_per_ks']:>9.4f} {r['est_occupancy_s']:>9.1f} "
            f"{r['undelivered']:>8.0f} {r['uncovered']:>6.0f}"
        )
    for r in rows:
        print("BENCH " + json.dumps(r), flush=True)

    dt_rows, verdict = delay_tolerance_rows(
        payload, args.antennas, args.altitude, steps_list[0],
        max(stales) or 2,
    )
    rows += dt_rows
    print("BENCH " + json.dumps(rows[-1]), flush=True)
    print(
        f"route-provenance audit: {verdict.n_windows} windows, "
        f"{verdict.n_payloads} payloads, {verdict.n_hops} hops, "
        f"{len(verdict.violations)} violation(s)"
    )

    rows += measured_rows(leaves, elems, args.antennas, steps_list[0],
                          args.altitude, reps)

    ratios = [
        r["throughput_ratio_d2_over_d1"]
        for r in rows
        if r["bench"] == "groundseg_pipeline_summary"
    ]
    if ratios:
        print(
            f"\npipelining win: depth-2 round throughput "
            f"{min(ratios):.2f}x-{max(ratios):.2f}x depth-1 across "
            f"{len(ratios)} sweep cells (>= 1.5x expected)"
        )

    if args.out:
        out_path = pathlib.Path(args.out)
        out_path.parent.mkdir(parents=True, exist_ok=True)
        out_path.write_text(json.dumps(rows, indent=1))
        print(f"wrote {len(rows)} rows to {out_path}")
    return rows, verdict


if __name__ == "__main__":
    main()
