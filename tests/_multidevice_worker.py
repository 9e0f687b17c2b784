"""Multi-device worker: runs the sim<->collective equivalence checks on 8
forced host devices. Launched as a subprocess by test_tdm_equivalence.py so
the main pytest process keeps its single default device.

Exit code 0 + final line "ALL-OK" on success.
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)

import functools
import random
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import fl, tdm
from repro.core.gossip import metropolis_weights, schedule_mixing_matrix
from repro.core.ptbfla_sim import run_schedule_getmeas
from repro.core.relation import Relation
from repro.core.schedule import TDMSchedule, hypercube_schedule
from repro.launch import mesh as mesh_lib

N = 8
mesh = Mesh(np.array(jax.devices()[:N]), ("node",))


def random_relation(rng: random.Random, n: int = N, p: float = 0.5) -> Relation:
    edges = [(i, j) for i in range(n) for j in range(i + 1, n) if rng.random() < p]
    return Relation.from_edges(edges, nodes=range(n))


def shmap(fn, in_specs, out_specs):
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def check(name, cond):
    if not cond:
        print(f"FAIL: {name}")
        sys.exit(1)
    print(f"ok: {name}")


# ---------------------------------------------------------------------------
# 1. collective get_meas == paper Algorithm 1 oracle (random relations)
# ---------------------------------------------------------------------------
def test_getmeas_equivalence():
    rng = random.Random(0)
    for case in range(25):
        rel = random_relation(rng)
        x = np.arange(N, dtype=np.float32) * 10 + 1  # node i holds 10i+1

        f = shmap(
            functools.partial(tdm.get_meas, rel=rel, axis_name="node", n=N),
            in_specs=P("node"),
            out_specs=(P("node"), P("node")),
        )
        peer_data, mask = jax.jit(f)(x)
        peer_data = np.asarray(peer_data).reshape(N, -1)
        mask = np.asarray(mask).reshape(N, -1)

        # oracle: paper-faithful simulator on the same relation
        sched = TDMSchedule((rel,))
        received, _ = run_schedule_getmeas(
            sched, {i: float(x[i]) for i in range(N)}, N, seed=case
        )
        for i in range(N):
            peers = rel.peers_of(i)
            got = [float(v) for v, m in zip(peer_data[i], mask[i]) if m]
            want = [received[i][0][p] for p in peers] if peers else []
            assert got == want, (case, i, got, want)
    check("collective get_meas == Algorithm 1 oracle (25 random relations)", True)


# ---------------------------------------------------------------------------
# 2. get1_meas == get_meas results (serialized vs multilink; same algebra)
# ---------------------------------------------------------------------------
def test_get1meas_equivalence():
    rng = random.Random(1)
    for case in range(10):
        rel = random_relation(rng)
        x = np.linspace(-1, 1, N).astype(np.float32)
        f_multi = shmap(
            functools.partial(tdm.get_meas, rel=rel, axis_name="node", n=N),
            in_specs=P("node"),
            out_specs=(P("node"), P("node")),
        )
        f_serial = shmap(
            functools.partial(tdm.get1_meas, rel=rel, axis_name="node", n=N),
            in_specs=P("node"),
            out_specs=(P("node"), P("node")),
        )
        a, ma = jax.jit(f_multi)(x)
        b, mb = jax.jit(f_serial)(x)
        assert np.array_equal(np.asarray(ma), np.asarray(mb))
        assert np.allclose(np.asarray(a), np.asarray(b)), case
    check("get1_meas (serialized) == get_meas (multilink) payloads", True)


# ---------------------------------------------------------------------------
# 3. gossip_avg == numpy W @ x (Metropolis weights)
# ---------------------------------------------------------------------------
def test_gossip_matches_mixing_matrix():
    rng = random.Random(2)
    for case in range(15):
        rel = random_relation(rng)
        x = np.random.default_rng(case).normal(size=(N, 4)).astype(np.float32)
        f = shmap(
            functools.partial(tdm.gossip_avg, rel=rel, axis_name="node", n=N),
            in_specs=P("node"),
            out_specs=P("node"),
        )
        got = np.asarray(jax.jit(f)(x)).reshape(N, 4)
        W = metropolis_weights(rel, N)
        want = W @ x.reshape(N, 4)
        assert np.allclose(got, want, atol=1e-5), case
    check("gossip_avg == W @ x for Metropolis W (15 random relations)", True)


# ---------------------------------------------------------------------------
# 4. schedule gossip == product of mixing matrices (paper P2, quantitative)
# ---------------------------------------------------------------------------
def test_schedule_gossip_composition():
    rng = random.Random(3)
    rels = tuple(random_relation(rng) for _ in range(3))
    sched = TDMSchedule(rels)
    x = np.random.default_rng(7).normal(size=(N, 3)).astype(np.float32)
    f = shmap(
        functools.partial(
            tdm.run_gossip_schedule, schedule=sched, axis_name="node", n=N
        ),
        in_specs=P("node"),
        out_specs=P("node"),
    )
    got = np.asarray(jax.jit(f)(x)).reshape(N, 3)
    W = schedule_mixing_matrix(sched, N)
    assert np.allclose(got, W @ x.reshape(N, 3), atol=1e-5)
    check("schedule gossip == product of per-slot mixing matrices", True)


# ---------------------------------------------------------------------------
# 5. hypercube schedule reaches exact consensus in log2(N) slots
# ---------------------------------------------------------------------------
def test_hypercube_consensus():
    sched = hypercube_schedule(N)
    x = np.random.default_rng(9).normal(size=(N,)).astype(np.float32)

    def body(v):
        for rel in sched:
            # pairwise average with hypercube partner: Metropolis on a
            # perfect matching is exactly 0.5/0.5
            v = tdm.gossip_avg(v, rel, "node", N)
        return v

    f = shmap(body, in_specs=P("node"), out_specs=P("node"))
    got = np.asarray(jax.jit(f)(x))
    assert np.allclose(got, x.mean(), atol=1e-5)
    check("hypercube TDM schedule -> exact consensus in log2(N) slots", True)


# ---------------------------------------------------------------------------
# 6. FL rounds: centralized == decentralized-clique (uniform avg)
# ---------------------------------------------------------------------------
def test_fl_round_equivalence():
    x = np.random.default_rng(11).normal(size=(N, 5)).astype(np.float32)
    f_cent = shmap(
        functools.partial(fl.centralized_round, axis_name="node"),
        in_specs=P("node"),
        out_specs=P("node"),
    )
    f_dec = shmap(
        functools.partial(fl.decentralized_round, axis_name="node", n=N),
        in_specs=P("node"),
        out_specs=P("node"),
    )
    a = np.asarray(jax.jit(f_cent)(x))
    b = np.asarray(jax.jit(f_dec)(x))
    assert np.allclose(a, b, atol=1e-5)
    assert np.allclose(a.reshape(N, 5), np.broadcast_to(x.reshape(N, 5).mean(0), (N, 5)), atol=1e-5)
    check("centralized FLA round == decentralized clique round == mean", True)


# ---------------------------------------------------------------------------
# 7. compressed exchange error bounds
# ---------------------------------------------------------------------------
def test_int8_exchange_error():
    rng = random.Random(4)
    rel = random_relation(rng, p=0.7)
    x = np.random.default_rng(13).normal(size=(N, 64)).astype(np.float32)
    f_ref = shmap(
        functools.partial(tdm.neighbor_sum, rel=rel, axis_name="node"),
        in_specs=P("node"),
        out_specs=P("node"),
    )
    f_q = shmap(
        functools.partial(tdm.neighbor_sum_int8, rel=rel, axis_name="node"),
        in_specs=P("node"),
        out_specs=P("node"),
    )
    ref = np.asarray(jax.jit(f_ref)(x))
    got = np.asarray(jax.jit(f_q)(x))
    rel_err = np.linalg.norm(got - ref) / max(np.linalg.norm(ref), 1e-9)
    assert rel_err < 0.02, rel_err
    check(f"int8-compressed neighbor_sum rel-err {rel_err:.4f} < 2%", True)


def test_topk_choco_converges():
    """CHOCO-Gossip with top-k compression: consensus under compressed
    absolute-value exchange (each round ships k=8 of 32 entries)."""
    rng = random.Random(5)
    rel = random_relation(rng, p=0.9)
    cfg = fl.TDMFLAConfig(compression="topk", topk_k=8, choco_gamma=0.4)
    x0 = np.random.default_rng(17).normal(size=(N, 32)).astype(np.float32)

    def rounds(x):
        res = None
        for _ in range(80):
            x, res = fl.tdm_mix(x, rel, "node", N, cfg, res)
        return x

    f = shmap(rounds, in_specs=P("node"), out_specs=P("node"))
    got = np.asarray(jax.jit(f)(x0)).reshape(N, 32)
    target = x0.reshape(N, 32).mean(0)
    err = np.linalg.norm(got - target) / np.linalg.norm(target)
    assert err < 0.05, err
    check(f"top-k CHOCO-Gossip TDM-FLA consensus err {err:.4f} < 5%", True)


def test_topk_error_feedback_on_deltas():
    """EF-top-k on additive deltas: summing compressed gradient-like deltas
    over many rounds recovers the uncompressed accumulation."""
    rng = random.Random(6)
    rel = random_relation(rng, p=0.8)
    g = np.random.default_rng(19).normal(size=(N, 32)).astype(np.float32)

    def rounds(grad):
        res = jnp.zeros_like(grad)
        acc = jnp.zeros_like(grad)
        for _ in range(40):
            summed, res = tdm.neighbor_sum_topk(grad, res, rel, "node", 8)
            acc = acc + summed
        return acc

    f = shmap(rounds, in_specs=P("node"), out_specs=P("node"))
    acc = np.asarray(jax.jit(f)(g)).reshape(N, 32)
    A = rel.adjacency(N).astype(np.float32)
    want = 40 * (A @ g.reshape(N, 32))
    err = np.linalg.norm(acc - want) / np.linalg.norm(want)
    assert err < 0.05, err
    check(f"EF top-k delta accumulation err {err:.4f} < 5%", True)


# ---------------------------------------------------------------------------
# 8. TDM-FLA on a Walker constellation converges to consensus
# ---------------------------------------------------------------------------
def test_walker_tdm_fla():
    from repro.constellation.scenario import ScenarioSpec, ShellSpec, build_scenario

    scn = build_scenario(
        ScenarioSpec(
            shells=(ShellSpec(planes=2, per_plane=N // 2),),
            n_ground=0,
            steps=10,
        )
    )
    sched = TDMSchedule(tuple(scn.relations()))
    x0 = np.random.default_rng(23).normal(size=(N, 6)).astype(np.float32)

    def run(x):
        for rel in sched:
            x, _ = fl.tdm_mix(x, rel, "node", N)
        return x

    f = shmap(run, in_specs=P("node"), out_specs=P("node"))
    got = np.asarray(jax.jit(f)(x0)).reshape(N, 6)
    err = fl.consensus_error(list(got))
    assert err < 0.05, err
    check(f"Walker-constellation TDM-FLA consensus err {err:.4f} < 5%", True)


# ---------------------------------------------------------------------------
# 8b. geometry-derived contact-plan relations == Algorithm 1 oracle, and they
#     drive a real fl_train TDM round (constellation subsystem end-to-end)
# ---------------------------------------------------------------------------
def test_contact_plan_equivalence():
    """Bit-equivalence of the constellation subsystem's relations: every
    non-empty contact-plan slot exchanged via the collective get_meas must
    match the paper-faithful simulator, like case 1 but with topologies
    from orbital geometry instead of random graphs."""
    from repro.constellation import contact_plan as cp
    from repro.constellation import orbits as orb

    geom = orb.WalkerDelta(
        total=N, planes=2, altitude_km=8062.0, inclination_deg=60.0
    )
    plan = cp.build_contact_plan(
        geom,
        duration_s=geom.period_s,
        step_s=geom.period_s / 6,
        max_range_km=14_000.0,
    )
    x = np.arange(N, dtype=np.float32) * 10 + 1
    checked = 0
    for t, rel in enumerate(plan.relations()):
        if len(rel) == 0:
            continue
        f = shmap(
            functools.partial(tdm.get_meas, rel=rel, axis_name="node", n=N),
            in_specs=P("node"),
            out_specs=(P("node"), P("node")),
        )
        peer_data, mask = jax.jit(f)(x)
        peer_data = np.asarray(peer_data).reshape(N, -1)
        mask = np.asarray(mask).reshape(N, -1)
        received, _ = run_schedule_getmeas(
            TDMSchedule((rel,)), {i: float(x[i]) for i in range(N)}, N, seed=t
        )
        for i in range(N):
            peers = rel.peers_of(i)
            got = [float(v) for v, m in zip(peer_data[i], mask[i]) if m]
            want = [received[i][0][p] for p in peers] if peers else []
            assert got == want, (t, i, got, want)
        checked += 1
    assert checked > 0
    check(f"contact-plan relations == Algorithm 1 oracle ({checked} slots)", True)


def test_constellation_drives_fl_round():
    """A geometry-derived slot relation drives one fl_train tdm-mode round
    on the host-device mesh (the acceptance path of the subsystem)."""
    from repro.configs import archs
    from repro.constellation import contact_plan as cp
    from repro.constellation import orbits as orb
    from repro.data import pipeline
    from repro.launch import fl_train
    from repro.models.config import ShapeConfig
    from repro.optim import adamw

    geom = orb.WalkerDelta(
        total=N, planes=2, altitude_km=8062.0, inclination_deg=60.0
    )
    plan = cp.build_contact_plan(
        geom,
        duration_s=geom.period_s,
        step_s=geom.period_s / 4,
        max_range_km=14_000.0,
    )
    cfg = archs.smoke_cfg(archs.get("mamba2-780m"))
    opt_cfg = adamw.OptConfig(peak_lr=5e-3, warmup_steps=2, decay_steps=100)
    fl_cfg = fl_train.FLConfig(mode="tdm", local_steps=1)
    shape = ShapeConfig("fl", "train", 32, 2)
    fl_mesh = mesh_lib.make_mesh((N,), ("data",))
    state = fl_train._stack_init(jax.random.PRNGKey(0), cfg, opt_cfg, N, fl_mesh)

    def batch_fn(rnd):
        per_node = []
        for sat in range(N):
            b = pipeline.host_batch(cfg, shape, step=rnd, seed=100 + sat)
            per_node.append({k: v[None] for k, v in b.items()})
        return {k: np.stack([pn[k] for pn in per_node]) for k in per_node[0]}

    state, logs = fl_train.run_constellation_fl(
        cfg, opt_cfg, fl_mesh, N, fl_cfg, plan, state, batch_fn, rounds=2
    )
    assert len(logs) == 2
    assert all(np.isfinite(l.loss) for l in logs)
    assert any(l.n_links > 0 for l in logs)
    check(
        f"constellation plan drove fl_train tdm rounds (losses "
        f"{[round(l.loss, 2) for l in logs]})",
        True,
    )


def test_optimized_schedule_fl_matches_greedy_bitwise():
    """The rate-aware schedule optimizer must not change *what* is exchanged,
    only when: with zero slew penalty and an antenna budget covering every
    step's degree, greedy and rate-aware emit the identical relation
    sequence, so run_constellation_fl produces bit-for-bit identical
    consensus distances and losses."""
    from repro.configs import archs
    from repro.constellation import contact_plan as cp
    from repro.constellation import orbits as orb
    from repro.data import pipeline
    from repro.launch import fl_train
    from repro.models.config import ShapeConfig
    from repro.optim import adamw

    geom = orb.WalkerDelta(
        total=N, planes=2, altitude_km=8062.0, inclination_deg=60.0
    )
    plan = cp.build_contact_plan(
        geom,
        duration_s=geom.period_s,
        step_s=geom.period_s / 4,
        max_range_km=14_000.0,
    )
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

    logs_by_mode = {}
    for optimize in ("greedy", "rate"):
        state = fl_train._stack_init(jax.random.PRNGKey(0), cfg, opt_cfg, N, fl_mesh)
        _, logs = fl_train.run_constellation_fl(
            cfg, opt_cfg, fl_mesh, N, fl_cfg, plan, state, batch_fn,
            rounds=2, optimize=optimize, antennas=N,
            payload_bytes=1 << 16, acquisition_s=0.0,
        )
        logs_by_mode[optimize] = logs

    g, r = logs_by_mode["greedy"], logs_by_mode["rate"]
    assert len(g) == len(r) == 2
    for lg, lr in zip(g, r):
        assert lg.n_links == lr.n_links and lg.alive == lr.alive
        assert lg.loss == lr.loss, (lg.loss, lr.loss)             # bit-for-bit
        assert lg.consensus == lr.consensus, (lg.consensus, lr.consensus)
    check(
        f"optimizer-enabled fl run == greedy bit-for-bit (consensus "
        f"{[f'{l.consensus:.3e}' for l in r]})",
        True,
    )


# ---------------------------------------------------------------------------
# 9. hierarchical (pod x data) gossip on a 2x4 mesh
# ---------------------------------------------------------------------------
def test_hierarchical_gossip():
    mesh2 = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("pod", "data"))
    intra = Relation.clique(list(range(4)))
    inter = Relation.clique(list(range(2)))
    x = np.random.default_rng(29).normal(size=(8, 3)).astype(np.float32)

    def body(v):
        return tdm.hierarchical_gossip(
            v, intra, inter, data_axis="data", pod_axis="pod", n_data=4, n_pods=2
        )

    f = jax.shard_map(
        body, mesh=mesh2, in_specs=P(("pod", "data")),
        out_specs=P(("pod", "data")), check_vma=False,
    )
    got = np.asarray(jax.jit(f)(x)).reshape(8, 3)
    assert np.allclose(got, x.reshape(8, 3).mean(0), atol=1e-5)
    check("hierarchical pod x data gossip == global mean", True)


if __name__ == "__main__":
    test_getmeas_equivalence()
    test_get1meas_equivalence()
    test_gossip_matches_mixing_matrix()
    test_schedule_gossip_composition()
    test_hypercube_consensus()
    test_fl_round_equivalence()
    test_int8_exchange_error()
    test_topk_choco_converges()
    test_topk_error_feedback_on_deltas()
    test_walker_tdm_fla()
    test_contact_plan_equivalence()
    test_constellation_drives_fl_round()
    test_optimized_schedule_fl_matches_greedy_bitwise()
    test_hierarchical_gossip()
    print("ALL-OK")
