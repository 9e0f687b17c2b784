"""Runner of the FL-round cells: builds one FL node per chip through the
program's own entry point, ``fl_train.run(TDMRun(...))``, and runs its
rounds.

Set-up makes the stacked node state on the chips in one jitted call from
the seed (weights from the configuration's reference module, optimizer
state from the program), then drives the first three rounds through the
window's own call and feed, reading after round 1 the first gradient as
the optimizer holds it and after round 3 the parameters' change. Those
compiled rounds and that state are what the window then runs.
"""

from __future__ import annotations

import collections
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

FIRST_ROUNDS = 3
QUEUED_ROUNDS = 2  # rounds the host lets wait on the device


class PacedCache:
    """The program's ``RoundFnCache`` as ``run_tdm_rounds`` sees it, with
    each round's compiled call wrapped so that at most ``QUEUED_ROUNDS``
    rounds wait on the device (the host waits for round ``r - 2`` before it
    dispatches round ``r``), and each round's losses are kept."""

    def __init__(self, cache):
        self.cache = cache
        self.n_nodes = cache.n_nodes
        self.inflight = collections.deque()
        self.losses = []

    def expected_collectives(self, rel, state):
        return self.cache.expected_collectives(rel, state)

    def __call__(self, rel, example_args=None):
        fn = self.cache(rel, example_args=example_args)

        def paced(state, batch):
            while len(self.inflight) >= QUEUED_ROUNDS:
                with TraceAnnotation("bench.wait_device"):
                    self.inflight.popleft().block_until_ready()
            with TraceAnnotation("bench.dispatch"):
                state, losses = fn(state, batch)
            self.inflight.append(losses)
            self.losses.append(losses)
            return state, losses

        return paced


class FLCell:
    def __init__(self, model, conf: dict, mix: dict, seed: int):
        from repro.configs import archs
        from repro.core.relation import Relation
        from repro.launch import fl_train
        from repro.launch import mesh as mesh_lib
        from repro.optim import adamw

        from bench import traffic

        self.model, self.conf, self.mix = model, conf, mix
        self.fl_train, self.adamw = fl_train, adamw
        n = mix["nodes"]
        self.n = n
        self.cfg = model.program_config(conf, archs)
        opt = dict(conf["assumed"]["optimizer"])
        opt.pop("name")
        self.opt_cfg = adamw.OptConfig(dtype=conf["assumed"]["opt_dtype"], **opt)
        self.mesh = mesh_lib.make_mesh((n,), ("data",))
        self.fl_cfg = fl_train.FLConfig(
            mode="tdm", local_steps=mix["local_steps"], compression=mix["compression"]
        )
        self.rel = Relation.from_edges([tuple(e) for e in mix["edges"]], nodes=range(n))
        self.pool = traffic.make_pool(mix, conf["vocab_size"], seed, encoder_features(model, conf))
        self.key = traffic.seed_words(seed)  # a raw threefry key, uint32[2]
        self.cache = PacedCache(fl_train.RoundFnCache(
            self.cfg, self.opt_cfg, self.mesh, n, self.fl_cfg))
        self.rounds_done = 0
        self.state = None

    # ------------------------------------------------------------------ state
    def make_state(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        n, adamw, opt_cfg, model, conf = self.n, self.adamw, self.opt_cfg, self.model, self.conf

        def make(key):
            params = model.init(key, conf)
            state = {
                "params": params,
                "opt": adamw.init_opt_state(params, opt_cfg),
                "step": jnp.zeros((), jnp.int32),
            }
            return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), state)

        out = NamedSharding(self.mesh, P("data"))
        self.state = jax.jit(make, out_shardings=out)(self.key)

    def batch_fn(self, offset: int):
        pool = self.pool

        def fn(r):
            with TraceAnnotation("bench.batch_fn"):
                return pool[(offset + r) % len(pool)]

        return fn

    def run_rounds(self, relations) -> int:
        """Dispatch one round per relation through ``fl_train.run``; returns
        the rounds dispatched (their outputs may still be on the way)."""
        fl_train = self.fl_train
        res = fl_train.run(fl_train.TDMRun(
            self.cache, self.state, relations, self.batch_fn(self.rounds_done),
            log_every=0,
        ))
        self.state = res.state
        self.rounds_done += res.n_rounds
        return res.n_rounds

    # ------------------------------------------------------------ set-up
    def setup(self) -> dict:
        """State, then the first rounds through the window's call; returns
        the program's readings for the comparison."""
        self.make_state()
        b1 = self.conf["assumed"]["optimizer"]["b1"]
        norms = jax.jit(jax.vmap(_leaf_norms))
        readings = {}
        for r in range(FIRST_ROUNDS):
            self.run_rounds([self.rel])
            if r == 0:
                readings["grad_norm"] = norms(self.state["opt"]["mu"]) / (1 - b1)
        delta = jax.jit(lambda P, k: jax.vmap(
            lambda p: _leaf_norms(jax.tree.map(jnp.subtract, p, self.model.init(k, self.conf)))
        )(P))
        readings["update_norm"] = delta(self.state["params"], self.key)
        readings["loss"] = jnp.stack(self.cache.losses[:FIRST_ROUNDS])
        readings = {k: np.asarray(v, np.float64) for k, v in readings.items()}
        self.cache.inflight.clear()
        return readings

    def warm_batches(self):
        return self.pool[:FIRST_ROUNDS]

    # ------------------------------------------------------------ window
    def window(self, seconds: float) -> tuple:
        """Rounds dispatched until the host clock passes ``seconds``, then
        drained. Returns (rounds, wall seconds)."""
        rel = self.rel

        def relations(deadline):
            while time.perf_counter() < deadline:
                yield rel

        with TraceAnnotation("bench.window"):
            t0 = time.perf_counter()
            rounds = self.run_rounds(relations(t0 + seconds))
            with TraceAnnotation("bench.drain"):
                jax.block_until_ready(self.state)
            wall = time.perf_counter() - t0
        self.cache.inflight.clear()
        return rounds, wall

    # ------------------------------------------------------------ counts
    def compiled_round(self):
        fn = self.cache.cache(self.rel)
        return fn.lower(self.state, self.pool[0]).compile()

    def round_flops(self) -> float:
        from bench import flops
        from repro.models import registry

        one = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype),
                           self.state["params"])
        batch = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape[2:], x.dtype),
                             self.pool[0])
        per_step = flops.train_step_flops(registry.bundle(self.cfg).loss_fn, one, batch)
        return per_step * self.n * self.mix["local_steps"]

    def free(self):
        """Drop the node state and the round programs. A loaded TPU
        program can keep its temporaries reserved while it lives (the
        ring's round compiles to 12.4 GiB of them), so the programs go too,
        and the reference that follows has the chips to itself."""
        self.state = None
        self.cache = None
        gc.collect()
        jax.clear_caches()


def encoder_features(model, conf):
    """(frames, width) of the encoder features an encoder-decoder
    configuration's batches carry, else None."""
    fn = getattr(model, "encoder_features", None)
    return fn(conf) if fn else None


def _leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])
