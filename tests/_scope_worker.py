"""Worker for the round-scope tests of test_telemetry.py, on 2 forced host
devices: two int8 TDM rounds of a 2-node ring through ``run_tdm_rounds``
with tracing and reconcile on, counting ``jax.block_until_ready`` calls.
Prints one JSON line: the compiled round's HLO text (reconcile mode keeps
the AOT-compiled executable in the round cache), the sync count, the names
of the spans recorded and the recorder's gauges. Launched as a subprocess
(the device count locks at the first jax init).
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=2 " + os.environ.get("XLA_FLAGS", "")
)

import json

import jax
import numpy as np

from repro import telemetry
from repro.configs import archs
from repro.core.relation import Relation
from repro.data import pipeline
from repro.launch import fl_train
from repro.launch import mesh as mesh_lib
from repro.models.config import ShapeConfig
from repro.optim import adamw

N = 2


def main():
    cfg = archs.smoke_cfg(archs.get("mamba2-780m"))
    opt_cfg = adamw.OptConfig(peak_lr=5e-3, warmup_steps=2, decay_steps=100)
    fl_cfg = fl_train.FLConfig(mode="tdm", local_steps=1, compression="int8")
    mesh = mesh_lib.make_mesh((N,), ("data",))
    rel = Relation.from_edges([(0, 1)], nodes=range(N))
    shape = ShapeConfig("fl", "train", 32, 2)

    def batch_fn(rnd):
        per_node = [
            pipeline.host_batch(cfg, shape, step=rnd, seed=100 + v) for v in range(N)
        ]
        return {k: np.stack([b[k][None] for b in per_node]) for k in per_node[0]}

    state = fl_train._stack_init(jax.random.PRNGKey(0), cfg, opt_cfg, N, mesh)
    cache = fl_train.RoundFnCache(cfg, opt_cfg, mesh, N, fl_cfg)
    syncs = []
    orig = jax.block_until_ready

    def counting(x):
        syncs.append(1)
        return orig(x)

    with telemetry.record_scope(tracing=True, reconcile=True) as rec:
        jax.block_until_ready = counting
        try:
            state, _ = fl_train.run_tdm_rounds(
                cache, state, [rel, rel], batch_fn, log_every=0
            )
        finally:
            jax.block_until_ready = orig
        jax.block_until_ready(state)
        spans = [s.name for s in rec.spans]
        gauges = telemetry.metrics_snapshot(rec)["gauges"]
    (compiled,) = cache._fns.values()
    print(json.dumps({
        "hlo": compiled.as_text(), "syncs": len(syncs), "spans": spans, "gauges": gauges,
    }))


if __name__ == "__main__":
    main()
