"""Collective-level benchmark of the paper's two TDM primitives on a real
device mesh (8 forced host devices): HLO collective bytes + op counts for

  get1meas   (serialized matchings — single-antenna baseline)
  getMeas    (parallel matchings — the paper's universal algorithm)
  getMeas+int8 (beyond-paper: quantized ISL payloads)
  hierarchical (pod x data two-level gossip)

and wall-clock on CPU as a sanity signal. The structural claim to verify:
both primitives move the SAME bytes for a given relation (the paper's
constant-factor gap is concurrency/scheduling, not volume), while int8
cuts payload bytes ~4x.

Run as its own process (device count lock):
  PYTHONPATH=src python -m benchmarks.tdm_collectives
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 " + os.environ.get("XLA_FLAGS", "")
)

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import tdm
from repro.core.relation import Relation
from repro.launch.hlo_stats import collective_stats

N = 8
SIZE = 1 << 16   # payload floats per node


def compile_and_stats(fn, x):
    mesh = jax.make_mesh((N,), ("node",))
    f = jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=P("node"), out_specs=P("node"), check_vma=False
    ))
    lowered = f.lower(x)
    compiled = lowered.compile()
    stats = collective_stats(compiled.as_text())
    # wall time (CPU, rough): run a few times
    f(x).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(5):
        out = f(x)
    out.block_until_ready()
    wall = (time.perf_counter() - t0) / 5
    return stats, wall


def main(argv=None):
    rel = Relation.clique(list(range(N)))
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(N, SIZE)).astype(np.float32)
    )

    variants = {
        "get1meas_serial": lambda v: tdm.get1_meas(v, rel, "node", N)[0].sum(0),
        "getmeas_multilink": lambda v: tdm.get_meas(v, rel, "node", N)[0].sum(0),
        "neighbor_sum_fp32": lambda v: tdm.neighbor_sum(v, rel, "node"),
        "neighbor_sum_int8": lambda v: tdm.neighbor_sum_int8(v, rel, "node"),
    }
    rows = {}
    print(f"{'variant':<22} {'coll bytes':>12} {'ops':>5} {'wall ms':>9}")
    for name, fn in variants.items():
        stats, wall = compile_and_stats(fn, x)
        rows[name] = dict(bytes=stats.total_bytes, ops=stats.total_count, wall=wall)
        print(f"{name:<22} {stats.total_bytes:>12.0f} {stats.total_count:>5.0f} "
              f"{wall*1e3:>9.2f}")

    same_volume = rows["get1meas_serial"]["bytes"] == rows["getmeas_multilink"]["bytes"]
    ratio = rows["neighbor_sum_fp32"]["bytes"] / max(rows["neighbor_sum_int8"]["bytes"], 1)
    print(f"\nsame bytes serial vs multilink (concurrency-only gap): {same_volume}")
    print(f"int8 payload reduction: {ratio:.2f}x (expect ~3.5-4x)")
    return rows


if __name__ == "__main__":
    main()
