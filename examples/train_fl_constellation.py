"""End-to-end driver: FL over a constellation's geometry-derived
time-varying ISL visibility — the paper's motivating deployment.

Two modes (``--mode``):

- ``tdm`` (default) — decentralized FL: 8 MEO satellites (= 8 forced host
  devices) in a 2-plane Walker pattern, each training a reduced LM on its
  OWN data shard; communication happens ONLY through the paper's universal
  TDM algorithm (getMeas -> matchings -> ppermute) over each contact-plan
  step's visibility relation. Mid-run a satellite failure restricts the
  slot relations (paper skip-slot semantics) and training continues.
- ``groundseg`` — the paper's *centralized* generic FLA over the ground
  segment: 6 satellites + 2 ground stations. Satellite updates ride
  store-and-forward multi-hop ISL relays to the ground sinks along
  earliest-delivery contact-graph routes, the sinks FedAvg (hierarchical:
  regional models, pooled over terrestrial backhaul every other round),
  and the global model floods back on the downlink slots.
  ``--pipeline-depth 2`` overlaps round r's downlink with round r+1's
  uplink inside one contact window (disjoint slot capacity);
  ``--max-staleness K`` lets undelivered payloads persist up to K windows
  (delivered late, they are down-weighted by the staleness decay).

The topology is NOT invented: orbits are propagated, ISLs require line of
sight past the Earth's limb and a range gate, ground links an elevation
mask, and the slot relations come straight from the contact plan.

Run:  PYTHONPATH=src python examples/train_fl_constellation.py [--mode groundseg]
      (add --rounds 2 for the CI smoke run)
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 " + os.environ.get("XLA_FLAGS", "")
)

import argparse

import jax
import numpy as np

from repro.configs import archs
from repro.constellation import cost
from repro.constellation.scenario import ScenarioSpec, ShellSpec, build_scenario
from repro.data import pipeline
from repro.launch import fl_train
from repro.models.config import ShapeConfig
from repro.optim import adamw
from repro.launch import mesh as mesh_lib


ROUNDS = 10
LOCAL_STEPS = 2
PAYLOAD_BYTES = 1 << 22     # ~4 MiB of smoke-model params per exchange


def setup(n_sats: int, n_ground: int = 0, rounds=ROUNDS):
    cfg = archs.smoke_cfg(archs.get("mamba2-780m"))
    opt_cfg = adamw.OptConfig(peak_lr=5e-3, warmup_steps=2, decay_steps=100)
    shape = ShapeConfig("fl", "train", 32, 4)   # per-node batch of 4 rows

    # --- geometry: O3b-style MEO shell, visibility from orbital mechanics,
    # packaged by the unified scenario factory (same sky as the serving
    # example and the groundseg benchmarks)
    scn = build_scenario(ScenarioSpec(
        shells=(ShellSpec(planes=2, per_plane=n_sats // 2),),
        n_ground=n_ground,
        steps=max(rounds, 4),
        max_range_km=14_000.0,
    ))
    return cfg, opt_cfg, shape, scn


def make_batch_fn(cfg, shape, n_nodes):
    def batch_fn(round_idx):
        per_node = []
        for sat in range(n_nodes):
            bs = [
                pipeline.host_batch(cfg, shape, step=round_idx * LOCAL_STEPS + h,
                                    seed=1000 + sat)
                for h in range(LOCAL_STEPS)
            ]
            per_node.append({
                k: np.stack([b[k] for b in bs]) for k in bs[0]
            })
        return {
            k: np.stack([pn[k] for pn in per_node]) for k in per_node[0]
        }

    return batch_fn


def main_tdm(rounds=ROUNDS):
    n_sats = 8
    cfg, opt_cfg, shape, scn = setup(n_sats, rounds=rounds)
    geom, plan = scn.geom, scn.plan
    fl_cfg = fl_train.FLConfig(mode="tdm", local_steps=LOCAL_STEPS)
    windows = plan.windows()
    est = cost.plan_cost(plan, PAYLOAD_BYTES, mode="getmeas")
    print(
        f"{n_sats} satellites, Walker delta {geom.planes}-plane @ "
        f"{geom.altitude_km:.0f} km (period {geom.period_s/60:.0f} min): "
        f"{len(windows)} contact windows, est. comm "
        f"{est.time_s:.2f} s / {est.bytes_on_isl/1e9:.2f} GB per orbit"
    )
    for w in windows[:4]:
        print(
            f"  contact {w.i}<->{w.j}  [{w.t_start_s/60.0:5.1f}, "
            f"{w.t_end_s/60.0:5.1f}] min  {w.mean_rate_bps/1e6:.0f} Mb/s"
        )

    mesh = mesh_lib.make_mesh((n_sats,), ("data",))
    state = fl_train._stack_init(
        jax.random.PRNGKey(0), cfg, opt_cfg, n_sats, mesh
    )
    alive = set(range(n_sats))

    def on_round(log):
        print(f"round {log.round:2d}  mean-loss {log.loss:7.4f}  "
              f"consensus-dist {log.consensus:.4f}  links {log.n_links}")
        if log.round == 6:
            alive.discard(3)
            print("  !! satellite 3 lost — rescheduling (skip-slot semantics)")

    res = fl_train.run(fl_train.ConstellationRun(
        cfg, opt_cfg, mesh, n_sats, fl_cfg, plan, state,
        make_batch_fn(cfg, shape, n_sats),
        rounds=rounds, alive=alive, on_round=on_round,
    ))
    state = res.state
    print(f"done — {res.n_rounds} rounds, surviving satellites converged "
          f"together "
          f"(consensus {fl_train.consensus_distance(state['params']):.4f})")


def main_groundseg(rounds=ROUNDS, pipeline_depth=1, max_staleness=0):
    n_sats = 6
    cfg, opt_cfg, shape, scn = setup(n_sats, n_ground=2, rounds=rounds)
    geom, plan, ground = scn.geom, scn.plan, scn.ground_stations
    n_nodes = scn.n_nodes
    sinks = scn.ground_ids
    fl_cfg = fl_train.FLConfig(mode="tdm", local_steps=LOCAL_STEPS)
    gs_cfg = fl_train.GroundSegConfig(
        mode="hierarchical", sink_sync_every=2,
        pipeline_depth=pipeline_depth, max_staleness_windows=max_staleness,
    )

    est = cost.groundseg_mode_costs(
        plan, sinks, PAYLOAD_BYTES, antennas=2, pipeline_depth=pipeline_depth
    )
    print(
        f"{n_sats} satellites + {len(ground)} ground sinks, Walker delta "
        f"{geom.planes}-plane @ {geom.altitude_km:.0f} km "
        f"(pipeline depth {pipeline_depth}, staleness horizon "
        f"{max_staleness}):"
    )
    for mode in ("centralized", "gossip_getmeas"):
        rc = est[mode]
        print(
            f"  {mode:<16} est round {rc.time_s:9.1f} s, "
            f"{rc.bytes_on_isl/1e9:.2f} GB on ISL"
        )

    mesh = mesh_lib.make_mesh((n_nodes,), ("data",))
    state = fl_train._stack_init(
        jax.random.PRNGKey(0), cfg, opt_cfg, n_nodes, mesh
    )
    alive = set(range(n_nodes))
    # lose a satellite one round before the end so at least one later round
    # actually exercises the rerouting path (rounds=2 -> fail after round 0)
    fail_round = min(6, rounds - 2)

    def on_round(log):
        print(
            f"round {log.round:2d}  sat-loss {log.loss:7.4f}  "
            f"consensus-dist {log.consensus:.4f}  "
            f"delivered {log.delivered}/{log.alive}  "
            f"covered {log.covered}  carried {log.carried}  "
            f"dropped {log.dropped}  "
            f"{'pooled' if log.pooled else 'regional'}"
        )
        if log.round == fail_round and fail_round >= 0:
            alive.discard(2)
            print("  !! satellite 2 lost — rerouting (skip-slot semantics)")

    res = fl_train.run(fl_train.GroundSegRun(
        cfg, opt_cfg, mesh, n_nodes, fl_cfg, gs_cfg, plan, state,
        make_batch_fn(cfg, shape, n_nodes),
        sinks=sinks, rounds=rounds, alive=alive, on_round=on_round,
        antennas=2, payload_bytes=PAYLOAD_BYTES,
    ))
    state = res.state
    survivors = [v for v in range(n_sats) if v in alive]
    sat_params = jax.tree.map(
        lambda x: np.asarray(x)[survivors], state["params"]
    )
    print("done — surviving satellites aggregated through the ground segment "
          f"(consensus {fl_train.consensus_distance(sat_params):.4f})")


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--mode", choices=("tdm", "groundseg"), default="tdm")
    p.add_argument("--rounds", type=int, default=ROUNDS,
                   help="FL rounds (2 for the CI smoke run)")
    p.add_argument("--pipeline-depth", type=int, default=1, choices=(1, 2),
                   help="groundseg: overlap round r's downlink with round "
                        "r+1's uplink in one contact window")
    p.add_argument("--max-staleness", type=int, default=0,
                   help="groundseg: windows an undelivered payload persists "
                        "before it is dropped and reported")
    p.add_argument("--trace", default=None,
                   help="write a Chrome trace (Perfetto) of this run, plus "
                        "a <trace>.metrics.json counter snapshot")
    args = p.parse_args()
    from repro import telemetry

    with telemetry.trace_scope(args.trace) as rec:
        if args.mode == "groundseg":
            main_groundseg(args.rounds, args.pipeline_depth, args.max_staleness)
        else:
            main_tdm(args.rounds)
        if args.trace:
            telemetry.write_metrics(f"{args.trace}.metrics.json", rec)
        counters = telemetry.counters_snapshot()
        if counters:
            print("telemetry counters:")
            for name in sorted(counters):
                print(f"  {name} = {counters[name]:g}")


if __name__ == "__main__":
    main()
