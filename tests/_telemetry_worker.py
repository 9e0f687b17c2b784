"""Multi-device worker for the flight-recorder telemetry: the acceptance
gates on 8 forced host devices — telemetry-disabled runs issue ZERO extra
host syncs and stay bit-identical to traced+reconciled runs, the default-on
collective counters equal the static oracles replayed window by window,
reconcile mode AOT-verifies every compiled round, and the exported Chrome
trace is valid. Launched as a subprocess by test_telemetry.py (device count
locks at first jax init).

Exit code 0 + final line "ALL-OK" on success.
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)

import json
import sys
import tempfile

import jax
import numpy as np

from repro import telemetry
from repro.configs import archs
from repro.constellation import contact_plan, orbits
from repro.data import pipeline
from repro.groundseg import aggregation, routing
from repro.launch import fl_train
from repro.models.config import ShapeConfig
from repro.optim import adamw
from repro.launch import mesh as mesh_lib

N_SATS, N_GS = 6, 2
N = N_SATS + N_GS
SINKS = frozenset(range(N_SATS, N))
PAYLOAD = 1 << 20

GS_CFG = fl_train.GroundSegConfig(
    mode="centralized", pipeline_depth=2, max_staleness_windows=2
)


def check(name, cond):
    if not cond:
        print(f"FAIL: {name}")
        sys.exit(1)
    print(f"ok: {name}")


def groundseg_plan(steps=10):
    geom = orbits.WalkerDelta(
        total=N_SATS, planes=2, altitude_km=8062.0, inclination_deg=60.0
    )
    gs = [
        orbits.GroundStation(0.0, 0.0, name="equator"),
        orbits.GroundStation(45.0, 120.0, name="midlat"),
    ]
    return contact_plan.build_contact_plan(
        geom,
        duration_s=geom.period_s,
        step_s=geom.period_s / steps,
        ground_stations=gs,
        max_range_km=16_000.0,
    )


def tdm_plan(steps=6):
    geom = orbits.WalkerDelta(
        total=N, planes=2, altitude_km=8062.0, inclination_deg=60.0
    )
    return contact_plan.build_contact_plan(
        geom,
        duration_s=geom.period_s,
        step_s=geom.period_s / steps,
        max_range_km=16_000.0,
    )


def _fl_setup():
    cfg = archs.smoke_cfg(archs.get("mamba2-780m"))
    opt_cfg = adamw.OptConfig(peak_lr=5e-3, warmup_steps=2, decay_steps=100)
    fl_cfg = fl_train.FLConfig(mode="tdm", local_steps=1)
    shape = ShapeConfig("fl", "train", 32, 2)
    mesh = mesh_lib.make_mesh((N,), ("data",))

    def batch_fn(rnd):
        per_node = []
        for sat in range(N):
            b = pipeline.host_batch(cfg, shape, step=rnd, seed=100 + sat)
            per_node.append({k: v[None] for k, v in b.items()})
        return {k: np.stack([pn[k] for pn in per_node]) for k in per_node[0]}

    return cfg, opt_cfg, fl_cfg, mesh, batch_fn


def _run_groundseg(plan, rounds, **kw):
    cfg, opt_cfg, fl_cfg, mesh, batch_fn = _fl_setup()
    state = fl_train._stack_init(jax.random.PRNGKey(0), cfg, opt_cfg, N, mesh)
    return fl_train.run_groundseg_fl(
        cfg, opt_cfg, mesh, N, fl_cfg, GS_CFG, plan, state, batch_fn,
        sinks=SINKS, rounds=rounds, antennas=2, payload_bytes=PAYLOAD, **kw
    )


def _run_tdm(plan, rounds, **kw):
    cfg, opt_cfg, fl_cfg, mesh, batch_fn = _fl_setup()
    state = fl_train._stack_init(jax.random.PRNGKey(0), cfg, opt_cfg, N, mesh)
    return fl_train.run_constellation_fl(
        cfg, opt_cfg, mesh, N, fl_cfg, plan, state, batch_fn,
        rounds=rounds, **kw
    )


def _n_buckets(state):
    return len({l.dtype.name for l in jax.tree.leaves(state["params"])})


# ---------------------------------------------------------------------------
# 1. telemetry disabled: counters still collected, but ZERO extra host syncs
#    (the FL run loops never block_until_ready) and nothing traced
# ---------------------------------------------------------------------------
def test_disabled_zero_host_syncs():
    gp, tp = groundseg_plan(), tdm_plan()
    calls = []
    orig = jax.block_until_ready

    def counting(x):
        calls.append(1)
        return orig(x)

    with telemetry.record_scope() as rec:
        jax.block_until_ready = counting
        try:
            gs_state, _ = _run_groundseg(gp, rounds=3, log_every=0)
            tdm_state, _ = _run_tdm(tp, rounds=2, log_every=0)
        finally:
            jax.block_until_ready = orig
        jax.block_until_ready((gs_state, tdm_state))
        c = dict(rec.counters)
        no_trace = rec.spans == [] and rec.events == []
    check(
        "telemetry off: zero block_until_ready host syncs across "
        "3 groundseg + 2 tdm rounds",
        not calls,
    )
    check("telemetry off: no spans or events recorded", no_trace)
    check(
        "default-on counters still collected "
        f"(groundseg.rounds={c.get('groundseg.rounds')}, "
        f"fl.rounds={c.get('fl.rounds')})",
        c.get("groundseg.rounds") == 3
        and c.get("fl.rounds") == 2
        and c.get("groundseg.collectives.collective-permute", 0) > 0
        and c.get("fl.collectives.collective-permute", 0) > 0,
    )


# ---------------------------------------------------------------------------
# 2. observability must not perturb training: params after a run with
#    telemetry off == params with tracing + reconcile on, bit for bit
# ---------------------------------------------------------------------------
def test_bit_identical_when_disabled():
    gp, tp = groundseg_plan(), tdm_plan()
    runs = {}
    for label, flags in (
        ("off", {}),
        ("on", dict(tracing=True, reconcile=True)),
    ):
        with telemetry.record_scope(**flags):
            gs_state, _ = _run_groundseg(gp, rounds=3)
            tdm_state, _ = _run_tdm(tp, rounds=2)
        runs[label] = (
            jax.tree.map(np.asarray, gs_state["params"]),
            jax.tree.map(np.asarray, tdm_state["params"]),
        )
    for i, which in enumerate(("groundseg", "tdm")):
        a = jax.tree.leaves(runs["off"][i])
        b = jax.tree.leaves(runs["on"][i])
        assert len(a) == len(b)
        for x, y in zip(a, b):
            assert np.array_equal(x, y), which
    check(
        "fused tdm + pipelined groundseg params bit-identical with "
        "telemetry off vs tracing+reconcile on",
        True,
    )


# ---------------------------------------------------------------------------
# 3. groundseg: recorded per-window collective counters == the static
#    oracle replayed through a twin router; reconcile verifies every
#    compiled window; payload lifecycle + trace export
# ---------------------------------------------------------------------------
def test_groundseg_counters_match_window_oracle_and_trace():
    plan = groundseg_plan()
    rounds = 3
    with telemetry.record_scope(tracing=True, reconcile=True) as rec:
        state, logs = _run_groundseg(plan, rounds=rounds)
        c = dict(rec.counters)

    # replay the deterministic router to rebuild each window's oracle
    base_rels = list(plan.schedule(antennas=2, payload_bytes=PAYLOAD).tdm)
    router = routing.MultiWindowRouter(
        N, SINKS,
        max_staleness_windows=GS_CFG.max_staleness_windows,
        pipeline_depth=GS_CFG.pipeline_depth,
    )
    want = {}
    programs = []
    for _ in range(rounds):
        wp = router.plan_window(base_rels, alive=set(range(N)))
        programs.append(wp)
        for kind, cnt in aggregation.expected_window_collectives(
            wp, _n_buckets(state), compression=GS_CFG.compression, pool=True
        ).items():
            want[kind] = want.get(kind, 0) + cnt
    for kind, cnt in want.items():
        got = c.get(f"groundseg.collectives.{kind}", 0)
        assert got == cnt, (kind, got, cnt)
    check(
        "recorded collective counters == expected_window_collectives "
        f"summed over {rounds} windows: {want}",
        True,
    )

    # route-provenance audit of the EXECUTED run: replay every payload's
    # hop trail through the twin programs, checked against the slot
    # relations, the decay**age staleness weights, and the lifecycle
    # events the traced run actually emitted
    verdict = telemetry.audit_window_programs(
        programs,
        decay=GS_CFG.staleness_decay,
        slots=base_rels,
        weights=[
            aggregation.staleness_sink_weights(
                wp.uplink, wp.delivered_ages, GS_CFG.staleness_decay
            )
            for wp in programs
        ],
        events=rec.events,
    )
    assert verdict.ok, [str(v) for v in verdict.violations]
    assert verdict.n_windows == rounds and verdict.events_checked > 0
    assert verdict.n_payloads == sum(len(wp.ages) for wp in programs)
    check(
        f"route-provenance audit green over the executed run: "
        f"{verdict.n_payloads} payloads / {verdict.n_hops} hops / "
        f"{verdict.events_checked} lifecycle events, 0 violations",
        True,
    )

    misses = c.get("groundseg.window_cache.misses", 0)
    hits = c.get("groundseg.window_cache.hits", 0)
    assert misses + hits == rounds and misses >= 1, (misses, hits)
    assert c.get("reconcile.checked", 0) == misses
    assert c.get("reconcile.mismatched", 0) == 0
    check(
        f"reconcile AOT-verified all {misses} compiled windows "
        "(0 mismatches)",
        True,
    )

    names = [s.name for s in rec.spans]
    assert names.count("groundseg.window") == rounds
    assert names.count("groundseg.plan_window") == rounds
    assert names.count("groundseg.compile") == misses
    retraces = [e for e in rec.events if e.name == "retrace"]
    assert len(retraces) == misses
    delivered = [e for e in rec.events if e.name == "payload.delivered"]
    assert len(delivered) == sum(l.delivered for l in logs)
    queued = [e for e in rec.events if e.name == "payload.queued"]
    assert len(queued) == c.get("groundseg.payloads.queued")
    check(
        f"payload lifecycle events: {len(queued)} queued, "
        f"{len(delivered)} delivered instants match the round logs",
        True,
    )

    with tempfile.TemporaryDirectory() as d:
        out = telemetry.write_trace(os.path.join(d, "trace.json"), rec)
        doc = json.loads(out.read_text())
    evs = doc["traceEvents"]
    assert evs and evs[0]["ph"] == "M"
    assert all(ev["ph"] in ("M", "X", "i", "C") for ev in evs)
    ts = [ev["ts"] for ev in evs]
    assert ts == sorted(ts)
    x_names = {ev["name"] for ev in evs if ev["ph"] == "X"}
    assert {"groundseg.window", "groundseg.compile"} <= x_names
    assert doc["otherData"]["counters"] == c
    check(
        f"exported Chrome trace valid ({len(evs)} events, sorted, "
        "window spans present)",
        True,
    )


# ---------------------------------------------------------------------------
# 4. tdm: per-round counters == the static edge-coloring oracle over the
#    plan's relations; the round cache reconciles on every miss
# ---------------------------------------------------------------------------
def test_tdm_counters_match_static_oracle():
    plan = tdm_plan()
    rounds = 4
    with telemetry.record_scope(tracing=True, reconcile=True) as rec:
        state, _ = _run_tdm(plan, rounds=rounds)
        c = dict(rec.counters)

    rels = plan.relations()
    reps = -(-rounds // max(len(rels), 1))
    rels = (rels * reps)[:rounds]
    want = 0
    topologies = set()
    for rel in rels:
        topologies.add(tuple(sorted(rel.pairs)))
        want += telemetry.expected_tdm_collectives(rel, _n_buckets(state))[
            "collective-permute"
        ]
    assert c.get("fl.rounds") == rounds
    got = c.get("fl.collectives.collective-permute", 0)
    assert got == want and want > 0, (got, want)
    misses = c.get("fl.round_cache.misses", 0)
    assert misses == len(topologies)
    assert misses + c.get("fl.round_cache.hits", 0) == rounds
    assert c.get("reconcile.checked", 0) == misses
    assert c.get("reconcile.mismatched", 0) == 0
    names = [s.name for s in rec.spans]
    assert names.count("fl.round") == rounds
    assert names.count("fl.compile") == misses
    check(
        f"tdm rounds: {got} recorded permutes == edge-coloring oracle over "
        f"{rounds} rounds ({misses} topologies compiled, all reconciled)",
        True,
    )


if __name__ == "__main__":
    test_disabled_zero_host_syncs()
    test_bit_identical_when_disabled()
    test_groundseg_counters_match_window_oracle_and_trace()
    test_tdm_counters_match_static_oracle()
    print("ALL-OK")
