"""Multi-device worker for the fused flat-buffer exchange engine: HLO-level
collective counts (M fused vs L×M per-leaf) and fused-vs-per-leaf
equivalence for every compression mode, on 8 forced host devices. Launched
as a subprocess by test_fused.py (device count locks at first jax init).

Exit code 0 + final line "ALL-OK" on success.
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)

import random
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import fl, fused, tdm
from repro.core.relation import Relation
from repro.core.schedule import ring
from repro.launch.hlo_stats import collective_stats
from repro.launch import mesh as mesh_lib

N = 8
mesh = Mesh(np.array(jax.devices()[:N]), ("node",))

# L=12 > 10 leaves, mixed shapes, all fp32 (single bucket => exactly M)
SHAPES = [
    (3, 5), (17,), (4, 4, 2), (128,), (33,), (2, 2),
    (64, 3), (7,), (5, 5), (11, 3), (9,), (256,),
]
L = len(SHAPES)


def make_tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        f"w{i}": jnp.asarray(rng.normal(size=(N,) + s).astype(np.float32))
        for i, s in enumerate(SHAPES)
    }


def round_fn(rel, cfg, **kw):
    def body(t):
        t = jax.tree.map(lambda x: x[0], t)
        if kw:
            out, _ = fused.fused_tdm_fla_round(t, rel, "node", N, cfg, **kw)
        else:
            out, _ = fl.tdm_fla_round(t, rel, "node", N, cfg)
        return jax.tree.map(lambda x: x[None], out)

    # check_vma=False: the Pallas quantization kernels have no replication
    # rule (same reason build_fl_round disables it)
    return jax.jit(
        jax.shard_map(
            body, mesh=mesh, in_specs=(P("node"),), out_specs=P("node"),
            check_vma=False,
        )
    )


def permute_count(fn, tree) -> float:
    stats = collective_stats(fn.lower(tree).compile().as_text())
    return stats.count_by_kind.get("collective-permute", 0.0)


def tree_equal(a, b) -> bool:
    return all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


def tree_rel_err(a, b) -> float:
    num = sum(
        float(np.square(np.asarray(x) - np.asarray(y)).sum())
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )
    den = sum(float(np.square(np.asarray(y)).sum()) for y in jax.tree.leaves(b))
    return (num / max(den, 1e-30)) ** 0.5


def random_relation(rng: random.Random, p: float = 0.5) -> Relation:
    edges = [(i, j) for i in range(N) for j in range(i + 1, N) if rng.random() < p]
    return Relation.from_edges(edges, nodes=range(N))


def check(name, cond):
    if not cond:
        print(f"FAIL: {name}")
        sys.exit(1)
    print(f"ok: {name}")


# ---------------------------------------------------------------------------
# 1. HLO collective counts: fused == M, per-leaf == L×M (the tentpole claim)
# ---------------------------------------------------------------------------
def test_hlo_collective_counts():
    tree = make_tree()
    for rel in (ring(N), Relation.clique(list(range(N)))):
        M = len(tdm.edge_coloring(rel))
        got_fused = permute_count(round_fn(rel, fl.TDMFLAConfig(fused=True)), tree)
        got_leaf = permute_count(round_fn(rel, fl.TDMFLAConfig(fused=False)), tree)
        assert got_fused == M, (got_fused, M)
        assert got_leaf == L * M, (got_leaf, L, M)
        # int8 ships payload + scales per matching: exactly 2M, still no L
        got_int8 = permute_count(
            round_fn(rel, fl.TDMFLAConfig(compression="int8", fused=True)), tree
        )
        assert got_int8 == 2 * M, (got_int8, M)
        # fused CHOCO packs values+indices into ONE int32 payload: exactly M
        # (the per-leaf path ships values and indices separately = 2LM)
        got_topk = permute_count(
            round_fn(rel, fl.TDMFLAConfig(compression="topk", fused=True)), tree
        )
        assert got_topk == M, (got_topk, M)
        # k=4 fits the smallest leaf; the collective count is k-independent
        got_topk_leaf = permute_count(
            round_fn(
                rel, fl.TDMFLAConfig(compression="topk", topk_k=4, fused=False)
            ),
            tree,
        )
        assert got_topk_leaf == 2 * L * M, (got_topk_leaf, L, M)
    check(
        f"HLO: fused = M permutes (topk packed = M too), per-leaf = {L}xM "
        f"(topk = 2x{L}xM), int8 fused = 2M",
        True,
    )


# ---------------------------------------------------------------------------
# 1b. mixed-dtype trees: every dtype bucket pays the same per-bucket count —
#     XLA must NOT combine the buckets' collectives, or the telemetry oracle
#     (and RoundFnCache's no-skip reconcile path) would be wrong
# ---------------------------------------------------------------------------
def test_mixed_dtype_hlo_counts():
    from repro import telemetry

    base = make_tree(seed=9)
    tree = {
        k: (v.astype(jnp.bfloat16) if i % 2 else v)
        for i, (k, v) in enumerate(base.items())
    }
    n_buckets = len({v.dtype.name for v in tree.values()})
    assert n_buckets == 2
    for rel in (ring(N), Relation.clique(list(range(N)))):
        for comp in ("none", "int8", "topk"):
            want = telemetry.expected_tdm_collectives(
                rel, n_buckets, compression=comp
            )["collective-permute"]
            got = permute_count(
                round_fn(rel, fl.TDMFLAConfig(compression=comp, fused=True)),
                tree,
            )
            assert got == want, (comp, got, want)
    check(
        "HLO: mixed f32+bf16 tree pays exactly per x M x n_buckets permutes "
        "for none/int8/topk (buckets never combined)",
        True,
    )


# ---------------------------------------------------------------------------
# 2. uncompressed fused == per-leaf, bit for bit (both primitives)
# ---------------------------------------------------------------------------
def test_uncompressed_bitwise():
    rng = random.Random(0)
    for case in range(8):
        rel = random_relation(rng)
        if len(rel) == 0:
            continue
        tree = make_tree(seed=case)
        for comm in ("getmeas", "get1meas"):
            a = round_fn(rel, fl.TDMFLAConfig(comm=comm, fused=True))(tree)
            b = round_fn(rel, fl.TDMFLAConfig(comm=comm, fused=False))(tree)
            assert tree_equal(a, b), (case, comm)
    check("uncompressed fused == per-leaf bitwise (getmeas + get1meas)", True)


# ---------------------------------------------------------------------------
# 3. int8: fused (blockwise, Metropolis) tracks exact gossip and the per-leaf
#    path within quantization tolerance; Pallas-interpret == jnp ref impl
# ---------------------------------------------------------------------------
def test_int8_tolerance():
    tree = make_tree(seed=3)
    rel = Relation.clique(list(range(N)))  # regular: per-leaf weights == Metropolis
    exact = round_fn(rel, fl.TDMFLAConfig(fused=True))(tree)
    got = round_fn(rel, fl.TDMFLAConfig(compression="int8", fused=True))(tree)
    err_exact = tree_rel_err(got, exact)
    assert err_exact < 0.02, err_exact
    per_leaf = round_fn(rel, fl.TDMFLAConfig(compression="int8", fused=False))(tree)
    err_leaf = tree_rel_err(got, per_leaf)
    assert err_leaf < 0.04, err_leaf
    check(
        f"int8 fused: vs exact gossip {err_exact:.4f} < 2%, "
        f"vs per-leaf int8 {err_leaf:.4f} < 4%",
        True,
    )


def test_int8_pallas_matches_ref_impl():
    tree = make_tree(seed=4)
    rel = ring(N)
    cfg = fl.TDMFLAConfig(compression="int8")
    a = round_fn(rel, cfg, quant_impl="pallas_interpret")(tree)
    b = round_fn(rel, cfg, quant_impl="ref")(tree)
    err = tree_rel_err(a, b)
    assert err < 1e-6, err
    check("int8 fused: Pallas(interpret) impl == jnp ref impl", True)


def test_topk_pallas_matches_ref_impl():
    tree = make_tree(seed=6)
    rel = ring(N)
    cfg = fl.TDMFLAConfig(compression="topk", topk_k=16)
    a = round_fn(rel, cfg, quant_impl="pallas_interpret")(tree)
    b = round_fn(rel, cfg, quant_impl="ref")(tree)
    # ~1-ulp slack: inlined jnp ref is FMA-contractable by XLA where the
    # opaque interpret-mode pallas_call boundary is not (the standalone
    # differential suite in test_kernels.py proves bitwise equality when
    # both sides are jitted in isolation)
    err = tree_rel_err(a, b)
    assert err < 1e-6, err
    check("topk fused: Pallas(interpret) impl == jnp ref impl (<1e-6)", True)


# ---------------------------------------------------------------------------
# 4. CHOCO top-k on the fused buffer converges to consensus (state carried
#    across rounds, k budget = topk_k × n_leaves)
# ---------------------------------------------------------------------------
def test_choco_fused_converges():
    # k = 16 x 12 leaves = 192 of 751 live entries (~25% density, same
    # regime as the per-leaf CHOCO test); gamma shrinks with density
    cfg = fl.TDMFLAConfig(compression="topk", topk_k=16, choco_gamma=0.3)
    rng = random.Random(5)
    rel = random_relation(rng, p=0.9)
    tree = make_tree(seed=5)

    def rounds(t):
        t = jax.tree.map(lambda x: x[0], t)
        res = None
        for _ in range(80):
            t, res = fused.fused_tdm_fla_round(t, rel, "node", N, cfg, res)
        return jax.tree.map(lambda x: x[None], t)

    f = jax.jit(
        jax.shard_map(
            rounds, mesh=mesh, in_specs=(P("node"),), out_specs=P("node"),
            check_vma=False,
        )
    )
    got = f(tree)
    errs = []
    for k in tree:
        arr = np.asarray(got[k]).reshape(N, -1)
        target = np.asarray(tree[k]).reshape(N, -1).mean(0)
        errs.append(np.linalg.norm(arr - target) / max(np.linalg.norm(target), 1e-9))
    worst = max(errs)
    assert worst < 0.05, worst
    check(f"CHOCO top-k fused consensus err {worst:.4f} < 5%", True)


# ---------------------------------------------------------------------------
# 5. hierarchical (pod × data) gossip on the fused engine: 2×4 mesh,
#    uncompressed bit-identical to per-leaf hierarchical_gossip, int8 within
#    quantization tolerance, HLO counts == the hierarchical oracle
# ---------------------------------------------------------------------------
N_PODS, N_DATA = 2, 4
mesh2 = Mesh(np.array(jax.devices()[:N]).reshape(N_PODS, N_DATA), ("pod", "data"))
INTRA = Relation.clique(list(range(N_DATA)))
INTER = Relation.from_edges([(0, 1)], nodes=range(N_PODS))


def hier_fn(compression, quant_impl="auto"):
    def body(t):
        t = jax.tree.map(lambda x: x[0], t)
        out = fused.fused_hierarchical_round(
            t, INTRA, INTER, "data", "pod", N_DATA, N_PODS,
            compression=compression, quant_impl=quant_impl,
        )
        return jax.tree.map(lambda x: x[None], out)

    return jax.jit(
        jax.shard_map(
            body, mesh=mesh2, in_specs=(P(("pod", "data")),),
            out_specs=P(("pod", "data")), check_vma=False,
        )
    )


def test_hierarchical_fused():
    tree = make_tree(seed=7)

    # per-leaf reference: tdm.hierarchical_gossip applied leaf by leaf
    def leaf_body(t):
        t = jax.tree.map(lambda x: x[0], t)
        out = jax.tree.map(
            lambda x: tdm.hierarchical_gossip(
                x, INTRA, INTER, "data", "pod", N_DATA, N_PODS
            ),
            t,
        )
        return jax.tree.map(lambda x: x[None], out)

    f_leaf = jax.jit(
        jax.shard_map(
            leaf_body, mesh=mesh2, in_specs=(P(("pod", "data")),),
            out_specs=P(("pod", "data")), check_vma=False,
        )
    )
    got_none = hier_fn("none")(tree)
    assert tree_equal(got_none, f_leaf(tree))
    # clique intra (exact pod mean) + single-edge inter (pairwise mean) ==
    # the global mean on every node, up to float summation order
    err_mean = max(
        float(
            np.abs(
                np.asarray(got_none[k])
                - np.asarray(tree[k]).mean(axis=0, keepdims=True)
            ).max()
        )
        for k in tree
    )
    assert err_mean < 1e-5, err_mean
    got_int8 = hier_fn("int8")(tree)
    err8 = tree_rel_err(got_int8, got_none)
    assert err8 < 0.02, err8
    a = hier_fn("int8", quant_impl="pallas_interpret")(tree)
    b = hier_fn("int8", quant_impl="ref")(tree)
    assert tree_rel_err(a, b) < 1e-6
    check(
        f"hierarchical fused: none == per-leaf bitwise (global-mean err "
        f"{err_mean:.1e}), int8 rel-err {err8:.4f} < 2%, interpret == ref",
        True,
    )


def test_hierarchical_hlo_counts():
    from repro import telemetry

    tree = make_tree(seed=8)
    for comp in ("none", "int8"):
        want = telemetry.expected_hierarchical_collectives(
            INTRA, INTER, 1, compression=comp
        )["collective-permute"]
        stats = collective_stats(
            hier_fn(comp, quant_impl="ref").lower(tree).compile().as_text()
        )
        got = stats.count_by_kind.get("collective-permute", 0)
        assert got == want, (comp, got, want)
    check(
        "HLO: hierarchical fused round == (M_intra + M_inter) x per permutes",
        True,
    )


# ---------------------------------------------------------------------------
# 6. end-to-end: build_fl_round(fused) == build_fl_round(per-leaf) bit for
#    bit on a real smoke model (19 leaves), through the full training round
# ---------------------------------------------------------------------------
def test_build_fl_round_end_to_end():
    from repro.configs import archs
    from repro.data import pipeline
    from repro.launch import fl_train
    from repro.models.config import ShapeConfig
    from repro.optim import adamw

    cfg = archs.smoke_cfg(archs.get("mamba2-780m"))
    opt_cfg = adamw.OptConfig(peak_lr=5e-3, warmup_steps=2, decay_steps=100)
    shape = ShapeConfig("fl", "train", 32, 2)
    fl_mesh = mesh_lib.make_mesh((N,), ("data",))
    rel = ring(N)

    def batch_fn():
        per_node = []
        for sat in range(N):
            b = pipeline.host_batch(cfg, shape, step=0, seed=100 + sat)
            per_node.append({k: v[None] for k, v in b.items()})
        return {k: np.stack([pn[k] for pn in per_node]) for k in per_node[0]}

    batch = batch_fn()
    outs = {}
    for fused_flag in (True, False):
        fl_cfg = fl_train.FLConfig(mode="tdm", local_steps=1, fused=fused_flag)
        state = fl_train._stack_init(
            jax.random.PRNGKey(0), cfg, opt_cfg, N, fl_mesh
        )
        step = fl_train.build_fl_round(cfg, opt_cfg, fl_mesh, N, fl_cfg, rel)
        outs[fused_flag] = step(state, batch)
    s_f, loss_f = outs[True]
    s_l, loss_l = outs[False]
    assert np.array_equal(np.asarray(loss_f), np.asarray(loss_l))
    assert tree_equal(s_f["params"], s_l["params"])
    check(
        f"build_fl_round fused == per-leaf bit-for-bit on mamba2 smoke "
        f"(loss {float(np.mean(np.asarray(loss_f))):.3f})",
        True,
    )


def test_build_hierarchical_fl_round_end_to_end():
    from repro.configs import archs
    from repro.data import pipeline
    from repro.launch import fl_train
    from repro.models.config import ShapeConfig
    from repro.optim import adamw

    cfg = archs.smoke_cfg(archs.get("mamba2-780m"))
    opt_cfg = adamw.OptConfig(peak_lr=5e-3, warmup_steps=2, decay_steps=100)
    shape = ShapeConfig("fl", "train", 32, 2)
    mesh2 = mesh_lib.make_mesh((N_PODS, N_DATA), ("pod", "data"))
    intra = Relation.clique(list(range(N_DATA)))
    inter = ring(N_PODS)

    def batch_fn():
        per_node = []
        for sat in range(N):
            b = pipeline.host_batch(cfg, shape, step=0, seed=100 + sat)
            per_node.append({k: v[None] for k, v in b.items()})
        return {k: np.stack([pn[k] for pn in per_node]) for k in per_node[0]}

    batch = batch_fn()
    outs = {}
    for comp in ("none", "int8"):
        fl_cfg = fl_train.FLConfig(mode="tdm", local_steps=1, compression=comp)
        state = fl_train._stack_init(
            jax.random.PRNGKey(0), cfg, opt_cfg, N, mesh2, ("pod", "data")
        )
        step = fl_train.build_hierarchical_fl_round(
            cfg, opt_cfg, mesh2, N_PODS, N_DATA, fl_cfg, intra, inter
        )
        new_state, losses = step(state, batch)
        outs[comp] = new_state["params"]
        losses = np.asarray(losses)
        assert losses.shape == (N,) and np.all(np.isfinite(losses))
        post = fl_train.consensus_distance(outs[comp])
        assert np.isfinite(float(post))
        check(
            f"hierarchical round ({comp}) loss "
            f"{float(losses.mean()):.3f}, node spread {float(post):.2e}",
            True,
        )
    err = tree_rel_err(outs["int8"], outs["none"])
    check(f"hierarchical builder int8 vs none rel err {err:.2e}", err < 0.02)
    try:
        fl_train.build_hierarchical_fl_round(
            cfg, opt_cfg, mesh2, N_PODS, N_DATA,
            fl_train.FLConfig(mode="tdm", compression="topk"), intra, inter,
        )
        check("hierarchical builder rejects topk", False)
    except ValueError:
        check("hierarchical builder rejects topk", True)


if __name__ == "__main__":
    test_hlo_collective_counts()
    test_mixed_dtype_hlo_counts()
    test_uncompressed_bitwise()
    test_int8_tolerance()
    test_int8_pallas_matches_ref_impl()
    test_topk_pallas_matches_ref_impl()
    test_choco_fused_converges()
    test_hierarchical_fused()
    test_hierarchical_hlo_counts()
    test_build_fl_round_end_to_end()
    test_build_hierarchical_fl_round_end_to_end()
    print("ALL-OK")
