"""Ground-segment round time: centralized / hierarchical FL through ground
sinks vs pure decentralized gossip, over Walker shells × ground-station
counts.

Two layers, emitted as ``BENCH {json}`` lines (and optionally ``--out``):

1. **Cost-oracle sweep** (any constellation size, pure Python): for each
   (planes × sats/plane) shell and ground-station count, route the
   materialized TDM schedule through
   :func:`repro.constellation.cost.groundseg_mode_costs` and report the
   estimated round time / ISL traffic of centralized, hierarchical, and
   both gossip primitives, plus delivery statistics from the router. Note
   the semantics: sink-based times are *delivery spans* (store-and-forward
   waits for geometry — idle gaps count), gossip times are link-busy
   seconds; traffic is directly comparable (relay ships one payload per
   hop, gossip one per directed pair per slot).

2. **Measured exchange** (8 forced host devices): the compiled
   ground-segment exchange (uplink relay -> sink FedAvg -> downlink
   broadcast on the fused buffers) and the equivalent per-slot fused
   gossip pass over the SAME schedule, wall-clocked and HLO-counted, so
   the oracle's centralized-vs-decentralized ordering can be checked
   against what the collectives actually cost on a mesh.

Run as its own process (device count lock):
  PYTHONPATH=src python -m benchmarks.groundseg_round_time --smoke
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
from repro.core import fl, tdm
from repro.groundseg import aggregation, routing
from repro.launch.hlo_stats import collective_stats

QUICK_SHELLS = [(2, 3), (2, 4)]
FULL_SHELLS = [(2, 3), (2, 4), (3, 4), (4, 5)]


def build_plan(planes, per_plane, n_gs, altitude_km, steps):
    """One scenario-factory deployment; the ground segment is the canonical
    ``scenario.GROUND_SITES`` prefix (this file used to carry its own copy)."""
    scn = build_scenario(ScenarioSpec(
        shells=(ShellSpec(
            planes=planes, per_plane=per_plane, altitude_km=altitude_km,
        ),),
        n_ground=n_gs,
        steps=steps,
    ))
    return scn.geom, scn.plan, scn.ground_ids


def oracle_rows(shells, gs_counts, payload_bytes, antennas, steps, altitude):
    rows = []
    for planes, per in shells:
        for n_gs in gs_counts:
            geom, plan, sinks = build_plan(planes, per, n_gs, altitude, steps)
            sched = plan.schedule(antennas=antennas, payload_bytes=payload_bytes)
            rels = list(sched.tdm)
            table = routing.earliest_delivery_routes(rels, plan.n_nodes, sinks)
            est = cost.groundseg_mode_costs(
                plan, sinks, payload_bytes, antennas=antennas
            )
            for mode, rc in est.items():
                rows.append(dict(
                    bench="groundseg_round_time",
                    planes=planes, per_plane=per, n_sats=geom.total,
                    n_gs=n_gs, mode=mode,
                    est_time_s=rc.time_s,
                    est_mbytes_isl=rc.bytes_on_isl / 1e6,
                    n_slots=rc.n_slots,
                    reachable=len(table.reachable()),
                    unreachable=len(table.unreachable()),
                    sched_span_s=sched.span_s,
                    sched_busy_s=sched.busy_s,
                ))
    return rows


# ---------------------------------------------------------------------------
# Measured exchange on the host-device mesh
# ---------------------------------------------------------------------------

def measure(fn, tree, reps):
    rec = telemetry.get_recorder()
    with rec.span("bench.compile", cat="compile"):
        compiled = fn.lower(tree).compile()
    stats = collective_stats(compiled.as_text())
    out = compiled(tree)
    jax.block_until_ready(out)
    with rec.span("bench.measure", cat="bench", reps=reps):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = compiled(tree)
        jax.block_until_ready(out)
        wall = (time.perf_counter() - t0) / reps
    rec.counter("bench.measured_cells")
    return stats, wall


def measured_rows(payload_bytes, payload_leaves, leaf_elems, antennas, steps,
                  altitude, reps, gs_counts):
    from benchmarks.fused_exchange import make_tree

    rows = []
    for n_gs in gs_counts:
        geom, plan, sinks = build_plan(2, 3, n_gs, altitude, steps)
        n = plan.n_nodes
        if n > len(jax.devices()):
            print(
                f"skipping measured cell {geom.total}sat+{n_gs}gs: needs "
                f"{n} devices, mesh has {len(jax.devices())} "
                "(oracle rows above still cover it)"
            )
            continue
        mesh = Mesh(np.array(jax.devices()[:n]), ("node",))
        sched = plan.schedule(antennas=antennas, payload_bytes=payload_bytes)
        rels = [r for r in sched.tdm]
        up = routing.build_relay_program(rels, n, sinks)
        down = routing.build_broadcast_program(rels, n, sinks)
        est = cost.groundseg_mode_costs(
            plan, sinks, payload_bytes, antennas=antennas
        )
        tree = make_tree(payload_leaves, leaf_elems, n=n)

        def wrap(body):
            return jax.jit(jax.shard_map(
                body, mesh=mesh, in_specs=(P("node"),), out_specs=P("node"),
                check_vma=False,
            ))

        def groundseg_body(compression):
            def body(t):
                t = jax.tree.map(lambda x: x[0], t)
                out = aggregation.groundseg_round(
                    t, up, down, "node", pool=True, compression=compression,
                )
                return jax.tree.map(lambda x: x[None], out)
            return body

        def gossip_body(t):
            t = jax.tree.map(lambda x: x[0], t)
            for rel in rels:
                if len(rel) == 0:
                    continue
                t, _ = fl.tdm_fla_round(t, rel, "node", n, fl.TDMFLAConfig())
            return jax.tree.map(lambda x: x[None], t)

        cells = {
            "centralized": wrap(groundseg_body("none")),
            "centralized_int8": wrap(groundseg_body("int8")),
            "gossip": wrap(gossip_body),
        }
        for engine, fn in cells.items():
            stats, wall = measure(fn, tree, reps)
            oracle = est["centralized" if engine.startswith("centralized")
                         else "gossip_getmeas"]
            row = dict(
                bench="groundseg_measured",
                n_sats=geom.total, n_gs=n_gs, engine=engine,
                permutes=stats.count_by_kind.get("collective-permute", 0),
                collective_bytes=stats.total_bytes,
                wall_ms=wall * 1e3,
                est_time_s=oracle.time_s,
                est_mbytes_isl=oracle.bytes_on_isl / 1e6,
            )
            rows.append(row)
            print(
                f"measured {geom.total}sat+{n_gs}gs {engine:<17} "
                f"permutes {row['permutes']:>5.0f}  "
                f"coll {stats.total_bytes/2**20:>7.2f} MB  "
                f"wall {wall*1e3:>8.2f} ms  oracle {oracle.time_s:>9.1f} s"
            )
            print("BENCH " + json.dumps(row), flush=True)
    return rows


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true", help="small sweep")
    p.add_argument("--full", action="store_true", help="larger shells")
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--antennas", type=int, default=2)
    p.add_argument("--altitude", type=float, default=8062.0)
    p.add_argument("--payload-mib", type=float, default=4.0)
    p.add_argument("--reps", type=int, default=None)
    p.add_argument("--out", default=None, help="write BENCH rows as json")
    p.add_argument("--trace", default=None,
                   help="write a Chrome trace (Perfetto) of this run")
    args = p.parse_args(argv)
    with telemetry.trace_scope(args.trace):
        rows = _main(args)
        print("TELEMETRY " + json.dumps(telemetry.counters_snapshot()),
              flush=True)
    return rows


def _main(args):
    if args.smoke:
        shells, gs_counts, reps = QUICK_SHELLS[:1], [1, 2], args.reps or 3
        leaves, elems = 8, 1 << 10
    elif args.full:
        shells, gs_counts, reps = FULL_SHELLS, [1, 2, 3, 4], args.reps or 10
        leaves, elems = 24, 1 << 12
    else:
        shells, gs_counts, reps = QUICK_SHELLS, [1, 2], args.reps or 5
        leaves, elems = 12, 1 << 10

    payload = int(args.payload_mib * (1 << 20))
    rows = oracle_rows(shells, gs_counts, payload, args.antennas, args.steps,
                       args.altitude)
    hdr = (f"{'shell':>6} {'gs':>3} {'mode':<17} {'est_time_s':>11} "
           f"{'MB_ISL':>8} {'slots':>6} {'reach':>6}")
    print(hdr)
    for r in rows:
        print(
            f"{r['planes']}x{r['per_plane']:<4} {r['n_gs']:>3} "
            f"{r['mode']:<17} {r['est_time_s']:>11.2f} "
            f"{r['est_mbytes_isl']:>8.1f} {r['n_slots']:>6} "
            f"{r['reachable']:>3}/{r['reachable'] + r['unreachable']:<3}"
        )
        print("BENCH " + json.dumps(r), flush=True)

    rows += measured_rows(payload, leaves, elems, args.antennas, args.steps,
                          args.altitude, reps, gs_counts)

    # headline: traffic ratio of the sink route vs gossip on the biggest cell
    cent = [r for r in rows if r["bench"] == "groundseg_round_time"
            and r["mode"] == "centralized" and r["reachable"] > 0]
    goss = {(r["planes"], r["per_plane"], r["n_gs"]): r for r in rows
            if r.get("mode") == "gossip_getmeas"}
    if cent:
        best = max(
            cent,
            key=lambda r: goss[(r["planes"], r["per_plane"], r["n_gs"])][
                "est_mbytes_isl"] / max(r["est_mbytes_isl"], 1e-9),
        )
        g = goss[(best["planes"], best["per_plane"], best["n_gs"])]
        ratio = g["est_mbytes_isl"] / max(best["est_mbytes_isl"], 1e-9)
        summary = dict(
            bench="groundseg_summary",
            planes=best["planes"], per_plane=best["per_plane"],
            n_gs=best["n_gs"], traffic_ratio_gossip_over_central=ratio,
        )
        rows.append(summary)
        print(
            f"\nbest ISL-traffic win: centralized ships {ratio:.1f}x fewer "
            f"bytes than gossip on {best['planes']}x{best['per_plane']} "
            f"+{best['n_gs']}gs"
        )
        print("BENCH " + json.dumps(summary), flush=True)

    if args.out:
        out_path = pathlib.Path(args.out)
        out_path.parent.mkdir(parents=True, exist_ok=True)
        out_path.write_text(json.dumps(rows, indent=1))
        print(f"wrote {len(rows)} rows to {out_path}")
    return rows


if __name__ == "__main__":
    main()
