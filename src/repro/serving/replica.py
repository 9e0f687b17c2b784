"""Replica decode: per-satellite KV/decode-state caches behind one fleet.

Two interchangeable decoders drive the serving engine:

- :class:`NullDecoder` — a pure-host deterministic token source. Zero jax,
  zero devices; it exists so the transport/scheduling/audit logic (the
  part this subsystem actually adds) is testable fast and its benchmark
  layer is bit-deterministic for nightly trending.
- :class:`ModelDecoder` — the real thing: one model replica per satellite,
  decoded as a *stacked* ``shard_map`` program over a ``("replica",)``
  device mesh (params replicated, caches and token streams carried with a
  leading replica axis, one per-lane squeeze/restack inside the body —
  the same idiom as ``launch/fl_train.py``'s stacked FL rounds).

Both expose the same two calls: ``prefill_waves({replica_idx: prompts})``
admits whole waves (the transformer decode cache keeps a single scalar
``pos`` per replica, so lanes inside one replica cannot stagger — wave
discipline per replica, continuous batching across the fleet), and
``step(active_mask)`` advances every busy replica one decode step.

:class:`ReplicaFleet` owns the mapping satellite-id → replica lane state:
admission queues, lane occupancy, wave admission, drain-on-churn.
"""

from __future__ import annotations

from collections import deque
from typing import Deque, Dict, List, Optional, Sequence

import numpy as np

from repro import telemetry
from repro.serving import requests as rq

_NULL_MOD = 65521  # largest prime < 2**16: cheap LCG modulus


class NullDecoder:
    """Deterministic host-side decoder (no model, no devices).

    First token of a lane is a hash of its prompt; each step advances a
    per-lane LCG. Tokens are meaningless but reproducible — exactly what
    the transport tests and the deterministic benchmark layer need.
    """

    def __init__(self, n_replicas: int, batch: int, vocab: int = 128):
        self.n_replicas = n_replicas
        self.batch = batch
        self.vocab = vocab
        self._state = np.zeros((n_replicas, batch), np.int64)

    def prefill_waves(
        self, waves: Dict[int, List[np.ndarray]]
    ) -> Dict[int, List[int]]:
        firsts: Dict[int, List[int]] = {}
        for ridx, prompts in waves.items():
            out: List[int] = []
            for lane, prompt in enumerate(prompts):
                h = (int(np.sum(prompt, dtype=np.int64)) * 31 + lane) % _NULL_MOD
                self._state[ridx, lane] = h
                out.append(h % self.vocab)
            firsts[ridx] = out
        return firsts

    def step(self, active: np.ndarray) -> np.ndarray:
        nxt = (self._state * 75 + 74) % _NULL_MOD
        self._state = np.where(active[:, None], nxt, self._state)
        return (self._state % self.vocab).astype(np.int64)


class ModelDecoder:
    """Stacked shard_map decode across a replica device mesh.

    Caches live stacked with a leading ``(R,)`` replica axis sharded over
    the mesh; ``prefill_waves`` runs the whole fleet through one padded
    prefill program (per prompt-length bucket, so jit retraces stay
    bounded) and merges each replica's new cache in under its admit flag;
    ``step`` advances only replicas flagged active — idle replicas keep
    their cache (and crucially their scalar ``pos``) frozen, so a replica
    can sit out contact gaps without walking its cache off ``max_len``.
    """

    def __init__(
        self,
        cfg,
        n_replicas: int,
        batch: int,
        max_len: int,
        seed: int = 0,
        mesh=None,
    ):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from repro.models import registry

        self._jax, self._jnp = jax, jnp
        self.cfg = cfg
        self.n_replicas = n_replicas
        self.batch = batch
        self.max_len = max_len
        self.bundle = registry.bundle(cfg)
        if mesh is None:
            devs = jax.devices()
            if len(devs) < n_replicas:
                raise ValueError(
                    f"ModelDecoder needs >= {n_replicas} devices "
                    f"(got {len(devs)}); use NullDecoder for host-only runs"
                )
            mesh = Mesh(np.array(devs[:n_replicas]), ("replica",))
        self.mesh = mesh

        # params replicated and caches stacked, both placed at creation:
        # each device only ever holds its own replica's cache
        self.params = jax.jit(
            lambda key: self.bundle.init(key)[0],
            out_shardings=NamedSharding(mesh, P()),
        )(jax.random.PRNGKey(seed))
        self._cache = jax.jit(
            lambda: jax.tree.map(
                lambda x: jnp.broadcast_to(x, (n_replicas,) + x.shape),
                self.bundle.init_cache(batch, max_len),
            ),
            out_shardings=NamedSharding(mesh, P("replica")),
        )()
        self._last = np.zeros((n_replicas, batch), np.int64)
        self._prefill_progs: Dict[int, object] = {}

        def decode_body(params, cache, tok, active):
            lane = jax.tree.map(lambda x: x[0], cache)
            logits, new = self.bundle.decode_fn(params, lane, {"token": tok[0]})
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            merged = jax.tree.map(
                lambda n, o: jnp.where(active[0], n, o), new, lane
            )
            return jax.tree.map(lambda x: x[None], merged), nxt[None]

        self._decode = jax.jit(
            jax.shard_map(
                decode_body,
                mesh=mesh,
                in_specs=(P(), P("replica"), P("replica"), P("replica")),
                out_specs=(P("replica"), P("replica")),
                check_vma=False,
            ),
            donate_argnums=(1,),
        )

    def _prefill_prog(self, plen: int):
        prog = self._prefill_progs.get(plen)
        if prog is not None:
            return prog
        jax, jnp = self._jax, self._jnp
        from jax.sharding import PartitionSpec as P

        def body(params, cache, toks, admit):
            lane = jax.tree.map(lambda x: x[0], cache)
            logits, new = self.bundle.prefill_fn(
                params, {"tokens": toks[0]}, self.max_len
            )
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            merged = jax.tree.map(
                lambda n, o: jnp.where(admit[0], n, o), new, lane
            )
            return jax.tree.map(lambda x: x[None], merged), nxt[None]

        prog = jax.jit(
            jax.shard_map(
                body,
                mesh=self.mesh,
                in_specs=(P(), P("replica"), P("replica"), P("replica")),
                out_specs=(P("replica"), P("replica")),
                check_vma=False,
            ),
            donate_argnums=(1,),
        )
        self._prefill_progs[plen] = prog
        return prog

    @staticmethod
    def _bucket(plen: int) -> int:
        b = 8
        while b < plen:
            b *= 2
        return b

    def prefill_waves(
        self, waves: Dict[int, List[np.ndarray]]
    ) -> Dict[int, List[int]]:
        jnp = self._jnp
        plen = self._bucket(max(len(p) for ps in waves.values() for p in ps))
        if plen + 1 > self.max_len:
            raise ValueError(
                f"prompt bucket {plen} does not fit max_len={self.max_len}"
            )
        toks = np.zeros((self.n_replicas, self.batch, plen), np.int32)
        admit = np.zeros((self.n_replicas,), np.bool_)
        for ridx, prompts in waves.items():
            admit[ridx] = True
            for lane, prompt in enumerate(prompts):
                toks[ridx, lane, plen - len(prompt):] = prompt  # left-pad
        self._cache, first = self._prefill_prog(plen)(
            self.params, self._cache, jnp.asarray(toks), jnp.asarray(admit)
        )
        first = np.asarray(first)
        out: Dict[int, List[int]] = {}
        for ridx, prompts in waves.items():
            out[ridx] = [int(first[ridx, lane]) for lane in range(len(prompts))]
            self._last[ridx] = first[ridx]
        return out

    def step(self, active: np.ndarray) -> np.ndarray:
        jnp = self._jnp
        self._cache, nxt = self._decode(
            self.params,
            self._cache,
            jnp.asarray(self._last[:, :, None].astype(np.int32)),
            jnp.asarray(active.astype(np.bool_)),
        )
        nxt = np.asarray(nxt, np.int64)
        self._last = np.where(active[:, None], nxt, self._last)
        return self._last.copy()


class ReplicaFleet:
    """Slot-aware continuous batching across the satellite replica set.

    Each replica runs wave discipline (a new wave is admitted only when its
    lanes are all free — the decode cache is one unit per replica); the
    *fleet* batches continuously: waves start and finish independently
    across replicas, and requests finishing early inside a wave release
    their response immediately while the wave's stragglers keep decoding.
    """

    def __init__(self, replica_ids: Sequence[int], batch: int, decoder):
        self.replica_ids: List[int] = sorted(int(s) for s in replica_ids)
        self.index = {sat: i for i, sat in enumerate(self.replica_ids)}
        self.batch = batch
        self.decoder = decoder
        self.queues: Dict[int, Deque[rq.InferenceRequest]] = {
            sat: deque() for sat in self.replica_ids
        }
        self.lanes: Dict[int, List[Optional[rq.InferenceRequest]]] = {
            sat: [None] * batch for sat in self.replica_ids
        }

    # ------------------------------------------------------------- queries
    def queued(self, sat: int) -> int:
        return len(self.queues[sat])

    def busy(self, sat: int) -> bool:
        return any(r is not None for r in self.lanes[sat])

    def active_requests(self, sat: int) -> List[rq.InferenceRequest]:
        return [r for r in self.lanes[sat] if r is not None and not r.done]

    def occupancy(self) -> float:
        """Active decode lanes / total lanes (fleet utilization gauge)."""
        total = len(self.replica_ids) * self.batch
        if total == 0:
            return 0.0
        busy = sum(
            1
            for sat in self.replica_ids
            for r in self.lanes[sat]
            if r is not None and not r.done
        )
        return busy / total

    # ----------------------------------------------------------- admission
    def enqueue(self, sat: int, req: rq.InferenceRequest) -> None:
        self.queues[sat].append(req)

    def admit(self, eligible) -> Dict[int, List[rq.InferenceRequest]]:
        """Start a wave on every eligible idle replica with queued work.

        Returns the admitted requests per satellite; each already carries
        its first decoded token (prefill emits it), so a ``max_new=1``
        request is complete straight out of admission.
        """
        waves: Dict[int, List[rq.InferenceRequest]] = {}
        prompts: Dict[int, List[np.ndarray]] = {}
        for sat in self.replica_ids:
            if sat not in eligible or self.busy(sat) or not self.queues[sat]:
                continue
            wave = [
                self.queues[sat].popleft()
                for _ in range(min(self.batch, len(self.queues[sat])))
            ]
            for lane, req in enumerate(wave):
                self.lanes[sat][lane] = req
            waves[sat] = wave
            prompts[self.index[sat]] = [r.prompt for r in wave]
        if not waves:
            return {}
        firsts = self.decoder.prefill_waves(prompts)
        for sat, wave in waves.items():
            for lane, req in enumerate(wave):
                req.out.append(int(firsts[self.index[sat]][lane]))
            if all(r.done for r in wave):
                # one-token requests: the wave completed at prefill, so the
                # lanes free immediately (tick would never see it active)
                self.lanes[sat] = [None] * self.batch
        return waves

    # -------------------------------------------------------------- decode
    def tick(self) -> Dict[int, List[rq.InferenceRequest]]:
        """One decode step for every replica with unfinished lanes.

        Returns the requests that just finished, keyed by satellite; fully
        finished waves release their lanes (the replica goes idle and can
        admit again next admission pass)."""
        active = np.zeros((len(self.replica_ids),), np.bool_)
        for i, sat in enumerate(self.replica_ids):
            active[i] = bool(self.active_requests(sat))
        if not active.any():
            return {}
        toks = self.decoder.step(active)
        finished: Dict[int, List[rq.InferenceRequest]] = {}
        for i, sat in enumerate(self.replica_ids):
            if not active[i]:
                continue
            for lane, req in enumerate(self.lanes[sat]):
                if req is None or req.done:
                    continue
                req.out.append(int(toks[i, lane]))
                if req.done:
                    finished.setdefault(sat, []).append(req)
            if all(r is None or r.done for r in self.lanes[sat]):
                self.lanes[sat] = [None] * self.batch
        telemetry.get_recorder().counter(
            "serve.decode.steps", float(int(active.sum()))
        )
        return finished

    # --------------------------------------------------------------- churn
    def drain(self, sat: int) -> List[rq.InferenceRequest]:
        """A replica lost visibility: abandon its wave and queue.

        Returns every request that still needs serving (mid-decode lanes
        and the admission queue); finished lanes keep nothing — their
        responses already left the fleet. The lane state clears so a
        re-admitted replica starts idle."""
        if sat not in self.index:
            return []
        out = [r for r in self.lanes[sat] if r is not None and not r.done]
        out.extend(self.queues[sat])
        self.lanes[sat] = [None] * self.batch
        self.queues[sat].clear()
        if out:
            telemetry.get_recorder().counter("serve.fleet.drained", len(out))
        return out


__all__ = ["ModelDecoder", "NullDecoder", "ReplicaFleet"]
