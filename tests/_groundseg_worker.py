"""Multi-device worker for the ground-segment subsystem: the acceptance
scenario end to end on 8 forced host devices — hierarchical FL over a
Walker constellation with 2 ground sinks (consensus decreasing), router
delivery of every reachable satellite inside the plan horizon, HLO-level
verification of the fused relay collective counts, and the int8 relay
path. Launched as a subprocess by test_groundseg.py (device count locks at
first jax init).

Exit code 0 + final line "ALL-OK" on success.
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)

import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import archs
from repro.constellation import contact_plan, cost, orbits
from repro.core.relation import Relation
from repro.data import pipeline
from repro.groundseg import aggregation, routing
from repro.launch import fl_train
from repro.launch.hlo_stats import collective_stats
from repro.models.config import ShapeConfig
from repro.optim import adamw
from repro.launch import mesh as mesh_lib

N_SATS, N_GS = 6, 2
N = N_SATS + N_GS
mesh = Mesh(np.array(jax.devices()[:N]), ("node",))


def check(name, cond):
    if not cond:
        print(f"FAIL: {name}")
        sys.exit(1)
    print(f"ok: {name}")


def walker_plan(steps=10):
    geom = orbits.WalkerDelta(
        total=N_SATS, planes=2, altitude_km=8062.0, inclination_deg=60.0
    )
    gs = [
        orbits.GroundStation(0.0, 0.0, name="equator"),
        orbits.GroundStation(45.0, 120.0, name="midlat"),
    ]
    return geom, contact_plan.build_contact_plan(
        geom,
        duration_s=geom.period_s,
        step_s=geom.period_s / steps,
        ground_stations=gs,
        max_range_km=16_000.0,
    )


SINKS = frozenset(range(N_SATS, N))


# ---------------------------------------------------------------------------
# 1. router delivers every reachable satellite within the plan horizon
# ---------------------------------------------------------------------------
def test_router_full_delivery():
    _, plan = walker_plan()
    sched = plan.schedule(antennas=2)
    rels = list(sched.tdm)
    table = routing.earliest_delivery_routes(rels, N, SINKS)
    up = routing.build_relay_program(rels, N, SINKS, table=table)
    reachable = table.reachable()
    delivered = set().union(*up.delivered.values()) if up.delivered else set()
    assert delivered == set(reachable), (delivered, reachable)
    horizon = len(rels) - 1
    for s in reachable:
        assert 0 <= table.routes[s].delivery_slot <= horizon
    # this MEO geometry covers everything — the acceptance scenario needs
    # every satellite's update at a sink
    assert len(reachable) == N_SATS, table.unreachable()
    check(
        f"router delivered {len(delivered)}/{N_SATS} satellites within "
        f"{len(rels)}-slot horizon",
        True,
    )


# ---------------------------------------------------------------------------
# 2. HLO: a compiled groundseg round issues exactly the statically-predicted
#    fused relay collectives (one permute per buffer per batch, 2x int8,
#    one masked psum per buffer when pooling)
# ---------------------------------------------------------------------------
def test_hlo_relay_collective_counts():
    _, plan = walker_plan()
    sched = plan.schedule(antennas=2)
    rels = list(sched.tdm)
    up = routing.build_relay_program(rels, N, SINKS)
    down = routing.build_broadcast_program(rels, N, SINKS)

    SHAPES = [(3, 5), (17,), (128,), (33,), (2, 2), (64, 3)]
    rng = np.random.default_rng(0)
    tree = {
        f"w{i}": jnp.asarray(rng.normal(size=(N,) + s).astype(np.float32))
        for i, s in enumerate(SHAPES)
    }
    for compression in ("none", "int8"):
        for pool in (True, False):
            def body(t):
                t = jax.tree.map(lambda x: x[0], t)
                out = aggregation.groundseg_round(
                    t, up, down, "node", pool=pool,
                    compression=compression, quant_impl="ref",
                )
                return jax.tree.map(lambda x: x[None], out)

            fn = jax.jit(
                jax.shard_map(
                    body, mesh=mesh, in_specs=(P("node"),),
                    out_specs=P("node"), check_vma=False,
                )
            )
            stats = collective_stats(fn.lower(tree).compile().as_text())
            want = aggregation.expected_collectives(
                up, down, 1, compression=compression, pool=pool
            )
            for kind, count in want.items():
                got = stats.count_by_kind.get(kind, 0)
                assert got == count, (compression, pool, kind, got, count)
    check("HLO: relay/broadcast collectives == static program counts", True)


# ---------------------------------------------------------------------------
# 3. aggregation numerics: pooled round -> covered nodes hold the exact
#    FedAvg mean; uncovered keep their params bit-for-bit
# ---------------------------------------------------------------------------
def test_fedavg_numerics():
    slots = [
        Relation.from_edges([(0, 1), (2, 6), (4, 5)], nodes=range(N)),
        Relation.from_edges([(1, 6), (5, 7), (3, 4)], nodes=range(N)),
        Relation.from_edges([(4, 7), (3, 6)], nodes=range(N)),
    ]
    up = routing.build_relay_program(slots, N, SINKS)
    down = routing.build_broadcast_program(slots, N, SINKS)
    assert set().union(*up.delivered.values()) == set(range(N_SATS))
    rng = np.random.default_rng(1)
    tree = {"w": jnp.asarray(rng.normal(size=(N, 37)).astype(np.float32))}

    def body(t):
        t = jax.tree.map(lambda x: x[0], t)
        out = aggregation.groundseg_round(t, up, down, "node", pool=True)
        return jax.tree.map(lambda x: x[None], out)

    fn = jax.jit(
        jax.shard_map(body, mesh=mesh, in_specs=(P("node"),),
                  out_specs=P("node"), check_vma=False)
    )
    x = np.asarray(tree["w"])
    y = np.asarray(fn(tree)["w"])
    want = x.mean(axis=0)  # 6 delivered sats + 2 sink models = all 8 rows
    cov = sorted(down.covered)
    uncov = [v for v in range(N) if v not in down.covered]
    assert np.allclose(y[cov], want, atol=1e-5)
    assert all(np.array_equal(y[v], x[v]) for v in uncov)
    # int8 relay tracks the exact mean within quantization tolerance
    def body8(t):
        t = jax.tree.map(lambda x: x[0], t)
        out = aggregation.groundseg_round(
            t, up, down, "node", pool=True, compression="int8",
            quant_impl="ref",
        )
        return jax.tree.map(lambda x: x[None], out)

    f8 = jax.jit(
        jax.shard_map(body8, mesh=mesh, in_specs=(P("node"),),
                  out_specs=P("node"), check_vma=False)
    )
    y8 = np.asarray(f8(tree)["w"])
    err = np.linalg.norm(y8[cov] - y[cov]) / max(np.linalg.norm(y[cov]), 1e-9)
    assert err < 0.02, err
    check(f"FedAvg numerics exact; int8 relay rel-err {err:.4f} < 2%", True)


# ---------------------------------------------------------------------------
# 3b. quantize-once relay: the SAME payloads delivered over a 3-hop chain
#     and over direct 1-hop slots produce BIT-IDENTICAL sink aggregates —
#     quantization error is paid once per route, independent of hop count —
#     and the aggregate equals a single-quantization numpy replay
# ---------------------------------------------------------------------------
def test_int8_relay_hop_count_independent():
    from repro.kernels.tdm_compress import ref as q_ref

    # B: 0 -> 1 -> 2 -> sink6 (payloads merge along the chain, 3 hops for
    # sat 0); A: the same three payloads ride direct 1-hop slots
    slots_chain = [
        Relation.from_edges([(0, 1)], nodes=range(N)),
        Relation.from_edges([(1, 2)], nodes=range(N)),
        Relation.from_edges([(2, 6)], nodes=range(N)),
    ]
    slots_direct = [
        Relation.from_edges([(0, 6)], nodes=range(N)),
        Relation.from_edges([(1, 6)], nodes=range(N)),
        Relation.from_edges([(2, 6)], nodes=range(N)),
    ]
    rng = np.random.default_rng(11)
    tree = {"w": jnp.asarray(rng.normal(size=(N, 96)).astype(np.float32))}
    outs = {}
    for name, slots in (("chain", slots_chain), ("direct", slots_direct)):
        up = routing.build_relay_program(slots, N, SINKS)
        down = routing.build_broadcast_program(slots, N, SINKS)
        assert set().union(*up.delivered.values()) == {0, 1, 2}

        def body(t, up=up, down=down):
            t = jax.tree.map(lambda x: x[0], t)
            out = aggregation.groundseg_round(
                t, up, down, "node", pool=True, compression="int8",
                quant_impl="ref",
            )
            return jax.tree.map(lambda x: x[None], out)

        fn = jax.jit(
            jax.shard_map(body, mesh=mesh, in_specs=(P("node"),),
                      out_specs=P("node"), check_vma=False)
        )
        outs[name] = np.asarray(fn(tree)["w"])
    # hop-count independence: the sinks' pooled global after 3-hop delivery
    # == after 1-hop delivery, bit for bit (the downlink floods differ in
    # reach between the two schedules, so only sink lanes are comparable)
    assert np.array_equal(outs["chain"][[6, 7]], outs["direct"][[6, 7]])
    # single-encode replay: shared scales are the pmax of every node's
    # blockwise scales; each delivered payload is quantized exactly once
    x = np.asarray(tree["w"])
    scales = np.max(
        [np.asarray(q_ref.blockwise_scales_ref(jnp.asarray(x[v]))) for v in
         range(N)],
        axis=0,
    )
    q = np.clip(np.rint(x / scales), -127, 127)
    want = (x[6] + (q[0] + q[1] + q[2]) * scales + x[7]) / 5.0
    got = outs["chain"][6]
    np.testing.assert_allclose(got, want, atol=1e-5)
    err = np.linalg.norm(got - (x[[0, 1, 2, 6, 7]].sum(0) / 5.0)) / max(
        np.linalg.norm(got), 1e-9
    )
    assert err < 0.02, err
    check(
        f"int8 relay: 3-hop == 1-hop bit-identical (single quantize/dequant "
        f"pair per route; vs exact FedAvg rel-err {err:.4f} < 2%)",
        True,
    )


# ---------------------------------------------------------------------------
# 4. acceptance: hierarchical FL over the Walker constellation with 2 ground
#    sinks — consensus distance decreases across rounds, centralized ends in
#    exact consensus on covered nodes, and the cost oracle emits sane
#    centralized-vs-decentralized numbers for the same plan
# ---------------------------------------------------------------------------
def _fl_setup():
    cfg = archs.smoke_cfg(archs.get("mamba2-780m"))
    opt_cfg = adamw.OptConfig(peak_lr=5e-3, warmup_steps=2, decay_steps=100)
    fl_cfg = fl_train.FLConfig(mode="tdm", local_steps=1)
    shape = ShapeConfig("fl", "train", 32, 2)
    fl_mesh = mesh_lib.make_mesh((N,), ("data",))

    def batch_fn(rnd):
        per_node = []
        for sat in range(N):
            b = pipeline.host_batch(cfg, shape, step=rnd, seed=100 + sat)
            per_node.append({k: v[None] for k, v in b.items()})
        return {k: np.stack([pn[k] for pn in per_node]) for k in per_node[0]}

    return cfg, opt_cfg, fl_cfg, fl_mesh, batch_fn


def test_hierarchical_fl_converges():
    geom, plan = walker_plan()
    cfg, opt_cfg, fl_cfg, fl_mesh, batch_fn = _fl_setup()
    gs_cfg = fl_train.GroundSegConfig(mode="hierarchical", sink_sync_every=2)
    state = fl_train._stack_init(
        jax.random.PRNGKey(0), cfg, opt_cfg, N, fl_mesh
    )
    state, logs = fl_train.run_groundseg_fl(
        cfg, opt_cfg, fl_mesh, N, fl_cfg, gs_cfg, plan, state, batch_fn,
        sinks=SINKS, rounds=4, antennas=2,
    )
    assert len(logs) == 4
    assert all(np.isfinite(l.loss) for l in logs)
    assert all(l.delivered == N_SATS for l in logs)
    assert all(l.unreachable == 0 for l in logs)
    # consensus decreases: local training spreads the nodes each round, the
    # sink round pulls them back — every pooled round must beat the
    # preceding unpooled round's spread, and the final pooled state must be
    # tighter than the first unpooled one
    spread = [l.consensus for l in logs if not l.pooled]
    tight = [l.consensus for l in logs if l.pooled]
    assert tight and spread
    assert max(tight) < min(spread), (tight, spread)
    check(
        f"hierarchical FL over Walker + 2 sinks: consensus pooled "
        f"{[f'{c:.1e}' for c in tight]} < unpooled "
        f"{[f'{c:.1e}' for c in spread]}",
        True,
    )


def test_centralized_exact_consensus_on_covered():
    geom, plan = walker_plan()
    cfg, opt_cfg, fl_cfg, fl_mesh, batch_fn = _fl_setup()
    gs_cfg = fl_train.GroundSegConfig(mode="centralized")
    state = fl_train._stack_init(
        jax.random.PRNGKey(0), cfg, opt_cfg, N, fl_mesh
    )
    state, logs = fl_train.run_groundseg_fl(
        cfg, opt_cfg, fl_mesh, N, fl_cfg, gs_cfg, plan, state, batch_fn,
        sinks=SINKS, rounds=2, antennas=2,
    )
    # every satellite was covered by the downlink each round -> after the
    # round they all hold the identical global model
    assert all(l.covered == N_SATS for l in logs)
    for leaf in jax.tree.leaves(state["params"]):
        arr = np.asarray(leaf)
        for v in range(1, N):
            assert np.array_equal(arr[0], arr[v])
    est = cost.groundseg_mode_costs(
        plan, SINKS, payload_bytes=1 << 20, antennas=2
    )
    assert est["centralized"].bytes_on_isl < est["gossip_getmeas"].bytes_on_isl
    check(
        "centralized FL: all covered satellites bit-identical to the "
        f"global model (relay traffic {est['centralized'].bytes_on_isl/1e6:.1f}"
        f" MB < gossip {est['gossip_getmeas'].bytes_on_isl/1e6:.1f} MB)",
        True,
    )


# ---------------------------------------------------------------------------
# 5. fault tolerance: a dead satellite drops out of routing (skip-slot) and
#    the survivors keep aggregating
# ---------------------------------------------------------------------------
def test_dead_satellite_skip_slot():
    geom, plan = walker_plan()
    cfg, opt_cfg, fl_cfg, fl_mesh, batch_fn = _fl_setup()
    gs_cfg = fl_train.GroundSegConfig(mode="centralized")
    state = fl_train._stack_init(
        jax.random.PRNGKey(0), cfg, opt_cfg, N, fl_mesh
    )
    alive = set(range(N))
    logs_seen = []

    def on_round(log):
        logs_seen.append(log)
        if log.round == 0:
            alive.discard(3)

    state, logs = fl_train.run_groundseg_fl(
        cfg, opt_cfg, fl_mesh, N, fl_cfg, gs_cfg, plan, state, batch_fn,
        sinks=SINKS, rounds=2, alive=alive, on_round=on_round, antennas=2,
    )
    assert logs[0].delivered == N_SATS and logs[0].alive == N_SATS
    assert logs[1].alive == N_SATS - 1
    assert logs[1].delivered == N_SATS - 1
    check("dead satellite dropped from routing; survivors aggregated", True)


# ---------------------------------------------------------------------------
# 6. pipelined multi-window engine: bit-identity at the trivial config,
#    HLO counts == the extended static oracle, delay-tolerant staleness
#    numerics, and the pipelined driver end to end
# ---------------------------------------------------------------------------
def _shard3(body):
    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P("node"),) * 3,
        out_specs=(P("node"),) * 3, check_vma=False,
    ))


def _window_fn(wp, pool=True, decay=0.5, compression="none"):
    def body(t, c, p):
        t = jax.tree.map(lambda x: x[0], t)
        c = jax.tree.map(lambda x: x[0], c)
        p = jax.tree.map(lambda x: x[0], p)
        out, nc, npend = aggregation.pipelined_window_round(
            t, c, p, wp, "node", pool=pool, staleness_decay=decay,
            compression=compression, quant_impl="ref",
        )
        return tuple(
            jax.tree.map(lambda x: x[None], z) for z in (out, nc, npend)
        )

    return _shard3(body)


def _zero_aux(tree):
    from repro.core import fused

    spec = fused.build_spec(jax.tree.map(lambda x: x[0], tree))
    return (aggregation.stacked_zero_buffers(spec, N),
            aggregation.stacked_zero_buffers(spec, N))


def test_pipelined_bit_identical_at_trivial_config():
    # depth 1, staleness 0: the pipelined engine must reproduce the PR 4
    # one-shot path BIT-FOR-BIT (same relay, same weights, same flood)
    slots = [
        Relation.from_edges([(0, 1), (2, 6), (4, 5)], nodes=range(N)),
        Relation.from_edges([(1, 6), (5, 7), (3, 4)], nodes=range(N)),
        Relation.from_edges([(4, 7), (3, 6)], nodes=range(N)),
    ]
    up = routing.build_relay_program(slots, N, SINKS)
    down = routing.build_broadcast_program(slots, N, SINKS)
    router = routing.MultiWindowRouter(N, SINKS)
    wp = router.plan_window(slots)
    rng = np.random.default_rng(3)
    tree = {"w": jnp.asarray(rng.normal(size=(N, 129)).astype(np.float32)),
            "b": jnp.asarray(rng.normal(size=(N, 7)).astype(np.float32))}
    for pool in (True, False):
        for compression in ("none", "int8"):
            def old_body(t, pool=pool, compression=compression):
                t = jax.tree.map(lambda x: x[0], t)
                out = aggregation.groundseg_round(
                    t, up, down, "node", pool=pool, compression=compression,
                    quant_impl="ref",
                )
                return jax.tree.map(lambda x: x[None], out)

            f_old = jax.jit(jax.shard_map(
                old_body, mesh=mesh, in_specs=(P("node"),),
                out_specs=P("node"), check_vma=False,
            ))
            carry, pend = _zero_aux(tree)
            y_old = f_old(tree)
            y_new, nc, _ = _window_fn(wp, pool=pool, compression=compression)(
                tree, carry, pend
            )
            for k in tree:
                assert np.array_equal(
                    np.asarray(y_old[k]), np.asarray(y_new[k])
                ), (pool, compression, k)
            assert all(not np.asarray(v).any() for v in nc.values())
    check("pipelined engine bit-identical to the one-shot path at "
          "depth 1 / staleness 0 (pooled and regional, none and int8)", True)


def test_pipelined_hlo_collective_counts():
    _, plan = walker_plan()
    sched = plan.schedule(antennas=2)
    rels = list(sched.tdm)
    router = routing.MultiWindowRouter(
        N, SINKS, max_staleness_windows=2, pipeline_depth=2
    )
    wp0 = router.plan_window(rels)   # warm-up: no downlink
    wp1 = router.plan_window(rels)   # steady: lagged downlink
    rng = np.random.default_rng(0)
    tree = {
        f"w{i}": jnp.asarray(rng.normal(size=(N,) + s).astype(np.float32))
        for i, s in enumerate([(3, 5), (17,), (128,), (33,)])
    }
    carry, pend = _zero_aux(tree)
    for wp in (wp0, wp1):
        for compression in ("none", "int8"):
            for pool in (True, False):
                fn = _window_fn(wp, pool=pool, compression=compression)
                stats = collective_stats(
                    fn.lower(tree, carry, pend).compile().as_text()
                )
                want = aggregation.expected_window_collectives(
                    wp, 1, compression=compression, pool=pool
                )
                for kind, count in want.items():
                    got = stats.count_by_kind.get(kind, 0)
                    assert got == count, (
                        wp.window, compression, pool, kind, got, count,
                    )
    check("HLO: pipelined window collectives == extended static oracle "
          "(warm-up + steady, none/int8, pooled/regional)", True)


def test_stale_delivery_numerics():
    # satellite 2 unreachable for exactly K windows, then delivers: the
    # sink FedAvg must include its ORIGINAL snapshot weighted decay**K
    K, DECAY = 2, 0.5
    iso = [Relation.from_edges(
        [(0, 6), (1, 6), (3, 6), (4, 7), (5, 7)], nodes=range(N)
    )]
    full = [Relation.from_edges(
        [(0, 6), (1, 6), (2, 6), (3, 6), (4, 7), (5, 7)], nodes=range(N)
    )]
    router = routing.MultiWindowRouter(N, SINKS, max_staleness_windows=K)
    rng = np.random.default_rng(7)
    tree = {"w": jnp.asarray(rng.normal(size=(N, 64)).astype(np.float32))}
    x0 = np.asarray(tree["w"]).copy()
    carry, pend = _zero_aux(tree)
    state = tree
    wps = []
    for w in range(K + 1):
        wp = router.plan_window(iso if w < K else full)
        wps.append(wp)
        state, carry, pend = _window_fn(wp, decay=DECAY)(state, carry, pend)
    last = wps[-1]
    assert last.delivered_ages[2] == K     # delivered at exactly the horizon
    assert not last.dropped
    # replay the weighted averages in numpy (params only change via floods)
    cur = x0.copy()
    for wp in wps:
        w = aggregation.staleness_sink_weights(wp.uplink, wp.delivered_ages,
                                               DECAY)
        num = sum(
            (DECAY ** wp.ages[s]) * (x0[s] if s == 2 else cur[s])
            for s in sorted(wp.delivered_ages)
        ) + cur[6] + cur[7]
        g = num / w.sum()
        for v in sorted(wp.downlink.covered | wp.uplink.sinks):
            cur[v] = g
    got = np.asarray(state["w"])
    np.testing.assert_allclose(got[6], cur[6], atol=1e-5)
    # beyond the horizon the payload is dropped, never delivered
    router2 = routing.MultiWindowRouter(N, SINKS, max_staleness_windows=1)
    for w in range(3):
        wp = router2.plan_window(iso)
    assert wp.dropped == {2: 2}
    assert router2.dropped_log[0].source == 2
    check(f"stale delivery at exactly K={K} windows lands with weight "
          f"decay^K; past-horizon payloads drop and report", True)


def test_pipelined_fl_end_to_end():
    geom, plan = walker_plan()
    cfg, opt_cfg, fl_cfg, fl_mesh, batch_fn = _fl_setup()
    gs_cfg = fl_train.GroundSegConfig(
        mode="centralized", pipeline_depth=2, max_staleness_windows=2,
    )
    state = fl_train._stack_init(
        jax.random.PRNGKey(0), cfg, opt_cfg, N, fl_mesh
    )
    state, logs = fl_train.run_groundseg_fl(
        cfg, opt_cfg, fl_mesh, N, fl_cfg, gs_cfg, plan, state, batch_fn,
        sinks=SINKS, rounds=3, antennas=2,
    )
    assert all(np.isfinite(log.loss) for log in logs)
    assert logs[0].covered == 0            # warm-up window: no global yet
    assert all(log.delivered == N_SATS for log in logs)
    assert all(log.covered == N_SATS for log in logs[1:])
    assert all(log.dropped == 0 for log in logs)
    # pipelined + centralized: after a steady-state round every covered
    # satellite holds the PREVIOUS round's global — all identical lanes
    for leaf in jax.tree.leaves(state["params"]):
        arr = np.asarray(leaf)
        for v in range(1, N_SATS):
            assert np.array_equal(arr[0], arr[v])
    check("pipelined depth-2 FL end to end: warm-up then steady coverage, "
          "satellites in exact consensus on the lagged global", True)


if __name__ == "__main__":
    test_router_full_delivery()
    test_hlo_relay_collective_counts()
    test_fedavg_numerics()
    test_int8_relay_hop_count_independent()
    test_hierarchical_fl_converges()
    test_centralized_exact_consensus_on_covered()
    test_dead_satellite_skip_slot()
    test_pipelined_bit_identical_at_trivial_config()
    test_pipelined_hlo_collective_counts()
    test_stale_delivery_numerics()
    test_pipelined_fl_end_to_end()
    print("ALL-OK")
