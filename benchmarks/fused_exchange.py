"""Fused vs per-leaf TDM exchange: collective counts (HLO-verified) and
per-round wall time, swept over model size × relation degree — on BOTH
synthetic leaf-count sweeps and real model registries
(``models/registry.py`` smoke variants: true leaf structures, mixed shapes,
scan-stacked layers), so the L×M claim is demonstrated on the trees the FL
drivers actually exchange.

The structural claim (core/fused.py): a per-leaf round issues L×M
collective-permutes for an L-leaf model over an M-matching relation (2M per
leaf-payload-component for compressed modes), while the fused flat-buffer
engine issues exactly M (2M for int8: payload + scales; top-k bit-packs
values + indices into ONE int32 payload so it stays at M) — independent
of L.
Collective counts come from the compiled HLO via
``launch.hlo_stats.collective_stats``; wall time is measured on the forced
8-host-device mesh (launch overhead dominates there exactly as it does on a
real mesh, which is the effect being benchmarked).

Emits one ``BENCH {json}`` line per measured cell plus a summary row, and
optionally writes the full row list to ``--out`` (the nightly workflow
uploads it so the perf trajectory is recorded).

Run as its own process (device count lock):
  PYTHONPATH=src python -m benchmarks.fused_exchange --smoke
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
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro import telemetry
from repro.core import fl, tdm
from repro.core.relation import Relation
from repro.core.schedule import ring
from repro.launch.hlo_stats import collective_stats

N = 8


def make_tree(n_leaves: int, leaf_elems: int, seed: int = 0, n: int = N):
    """Synthetic L-leaf model, stacked on the node axis. Shapes are jittered
    (+leaf index) so no two leaves are identical arrays XLA could CSE.
    (Also the payload generator for benchmarks/groundseg_round_time.py.)"""
    rng = np.random.default_rng(seed)
    return {
        f"w{i:03d}": jnp.asarray(
            rng.normal(size=(n, leaf_elems + i)).astype(np.float32)
        )
        for i in range(n_leaves)
    }


def make_registry_tree(arch_name: str):
    """A REAL model's parameter pytree (smoke-sized registry variant),
    stacked on the node axis — the exact tree ``launch/fl_train`` ships
    through the exchange engine."""
    from repro.configs import archs
    from repro.models import registry

    cfg = archs.smoke_cfg(archs.get(arch_name))
    params, _ = registry.bundle(cfg).init(jax.random.PRNGKey(0))
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (N,) + x.shape), params
    )


def model_cells(names):
    """(label, tree, n_leaves, elems_per_node, min_leaf) for synthetic specs
    ``(n_leaves, leaf_elems)`` and registry arch-name strings alike.
    ``min_leaf`` bounds the per-leaf top-k payload (``jax.lax.top_k``
    requires k <= leaf size; the fused engine has no such limit)."""
    cells = []
    for spec in names:
        if isinstance(spec, str):
            tree = make_registry_tree(spec)
            label = spec
        else:
            n_leaves, leaf_elems = spec
            tree = make_tree(n_leaves, leaf_elems)
            label = f"synth-L{n_leaves}"
        leaves = jax.tree.leaves(tree)
        sizes = [int(np.prod(l.shape[1:])) for l in leaves]
        cells.append((label, tree, len(leaves), sum(sizes), min(sizes)))
    return cells


def relations():
    return {
        "ring": ring(N),                                   # degree 2
        "circ4": Relation.from_edges(
            [(i, (i + d) % N) for i in range(N) for d in (1, 2)]
        ),                                                 # degree 4
        "clique": Relation.clique(list(range(N))),         # degree 7
    }


def build_round_fn(mesh, rel, cfg):
    def body(t):
        t = jax.tree.map(lambda x: x[0], t)
        out, _ = fl.tdm_fla_round(t, rel, "node", N, cfg)
        return jax.tree.map(lambda x: x[None], out)

    # check_vma=False: the fused int8 path may lower through pallas_call,
    # which has no shard_map replication rule
    return jax.jit(
        jax.shard_map(
            body, mesh=mesh, in_specs=(P("node"),), out_specs=P("node"),
            check_vma=False,
        )
    )


def measure(fn, tree, reps: int):
    # time the AOT executable itself — fn(tree) would re-trace and compile
    # a second copy through the jit dispatch cache
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


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true", help="single small cell")
    p.add_argument("--full", action="store_true", help="paper-size sweeps")
    p.add_argument("--reps", type=int, default=None)
    p.add_argument("--out", default=None, help="write BENCH rows as json")
    p.add_argument("--trace", default=None,
                   help="write a Chrome trace (Perfetto) of this run")
    p.add_argument("--report", default=None, metavar="PREFIX",
                   help="write PREFIX.md/.json mission report of this run")
    args = p.parse_args(argv)
    with telemetry.trace_scope(args.trace):
        rows = _main(args)
        print("TELEMETRY " + json.dumps(telemetry.counters_snapshot()),
              flush=True)
        if args.report:
            from repro.telemetry.report import write_report

            md, js = write_report(
                args.report,
                title="fused exchange bench",
                extra={
                    "bench": "fused_exchange",
                    "n_rows": len(rows),
                    "args": {
                        "smoke": args.smoke, "full": args.full,
                        "reps": args.reps,
                    },
                },
            )
            print(f"wrote mission report to {md} and {js}")
    return rows


def _main(args):
    if args.smoke:
        models = [(12, 1 << 10), "mamba2-780m"]
        rel_names = ["ring", "clique"]
        modes = ["none", "int8", "topk"]
        reps = args.reps or 3
    elif args.full:
        models = [
            (12, 1 << 10), (48, 1 << 12), (96, 1 << 14),
            "mamba2-780m", "gemma2-9b", "qwen3-moe-30b-a3b",
        ]
        rel_names = ["ring", "circ4", "clique"]
        modes = ["none", "int8", "topk"]
        reps = args.reps or 10
    else:
        models = [(12, 1 << 10), (48, 1 << 12), "mamba2-780m", "gemma2-9b"]
        rel_names = ["ring", "clique"]
        modes = ["none", "int8", "topk"]
        reps = args.reps or 5

    mesh = Mesh(np.array(jax.devices()[:N]), ("node",))
    rels = relations()
    rows = []
    print(
        f"{'model':<16} {'rel':<7} {'mode':<5} {'engine':<8} "
        f"{'permutes':>8} {'coll MB':>8} {'wall ms':>9}"
    )
    for label, tree, n_leaves, elems, min_leaf in model_cells(models):
        for rel_name in rel_names:
            rel = rels[rel_name]
            n_matchings = len(tdm.edge_coloring(rel))
            for mode in modes:
                cell = {}
                # per-leaf top-k caps k at the smallest leaf (top_k errors
                # above it); the collective COUNT is k-independent, so the
                # permute comparison is unaffected
                topk_k = min(64, min_leaf)
                for engine in ("perleaf", "fused"):
                    cfg = fl.TDMFLAConfig(
                        compression=mode, topk_k=topk_k,
                        fused=(engine == "fused"),
                    )
                    fn = build_round_fn(mesh, rel, cfg)
                    stats, wall = measure(fn, tree, reps)
                    permutes = stats.count_by_kind.get("collective-permute", 0)
                    row = dict(
                        bench="fused_exchange",
                        model=label,
                        n_leaves=n_leaves,
                        elems=elems,
                        relation=rel_name,
                        n_matchings=n_matchings,
                        mode=mode,
                        engine=engine,
                        permutes=permutes,
                        collective_bytes=stats.total_bytes,
                        wall_ms=wall * 1e3,
                    )
                    rows.append(row)
                    cell[engine] = row
                    print(
                        f"{label:<16} {rel_name:<7} "
                        f"{mode:<5} {engine:<8} {permutes:>8.0f} "
                        f"{stats.total_bytes/2**20:>8.2f} {wall*1e3:>9.2f}"
                    )
                    print("BENCH " + json.dumps(row), flush=True)
                speedup = cell["perleaf"]["wall_ms"] / max(
                    cell["fused"]["wall_ms"], 1e-9
                )
                summary = dict(
                    bench="fused_exchange_summary",
                    model=label,
                    n_leaves=n_leaves,
                    elems=elems,
                    relation=rel_name,
                    mode=mode,
                    n_matchings=n_matchings,
                    permutes_perleaf=cell["perleaf"]["permutes"],
                    permutes_fused=cell["fused"]["permutes"],
                    permute_reduction=cell["perleaf"]["permutes"]
                    / max(cell["fused"]["permutes"], 1),
                    speedup=speedup,
                )
                rows.append(summary)
                print("BENCH " + json.dumps(summary), flush=True)

    # headline: uncompressed cells must show M vs L*M and a wall-time win
    best = max(
        (r for r in rows if r["bench"] == "fused_exchange_summary"),
        key=lambda r: r["speedup"],
    )
    print(
        f"\nbest fused speedup: {best['speedup']:.2f}x "
        f"({best['model']} L={best['n_leaves']}, {best['relation']}, "
        f"mode={best['mode']}; permutes {best['permutes_perleaf']:.0f} -> "
        f"{best['permutes_fused']:.0f})"
    )
    if args.out:
        # summary-object form ({bench, rows, telemetry}) so
        # check_regression can trend the flight-recorder counters too
        out_path = pathlib.Path(args.out)
        out_path.parent.mkdir(parents=True, exist_ok=True)
        out_path.write_text(json.dumps({
            "bench": "fused_exchange",
            "rows": rows,
            "telemetry": telemetry.counters_snapshot(),
        }, indent=1))
        print(f"wrote {len(rows)} rows to {out_path}")
    return rows


if __name__ == "__main__":
    main()
