"""PTB-FLA training mode: satellites = node groups, each training on local
data, communicating ONLY via the paper's generic algorithms.

Implementation: parameters get a leading ``node`` axis sharded over the
mesh's node axis; one ``shard_map`` spans local compute + the TDM exchange,
so the per-slot relation literally becomes the collective schedule
(matchings -> ppermute, DESIGN.md §3). Three modes:

- ``centralized``   — FedAvg via all-reduce-mean every H steps
- ``decentralized`` — clique gossip (the paper's getMeas evaluation case)
- ``tdm``           — gossip over an arbitrary TDM schedule (constellation
                      visibility, ring, hypercube, ...), optionally int8 /
                      top-k (CHOCO) compressed

Every round, whatever its exchange (gossip, hierarchical gossip, ground
sinks, pipelined windows), is one program built by ``_round_program``, and
every round loop keeps its programs in one compile-on-miss cache
(``_ProgramCache``).

Time-varying schedules: :class:`RoundFnCache` + :func:`run_tdm_rounds` drive
one FL round per slot relation, recompiling only on unseen topologies;
:func:`run_constellation_fl` feeds them straight from a geometry-derived
:class:`~repro.constellation.contact_plan.ContactPlan` (the paper's actual
deployment — occluded satellites simply have no pairs that slot).

Fault tolerance: a failed/occluded satellite is dropped from the slot's
relation (``Relation.restrict``) — the paper's skip-slot semantics — and the
others keep training; its params re-sync through later gossip rounds.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import telemetry
from repro.core import fl, tdm
from repro.core.relation import Relation
from repro.launch import flops
from repro.models import registry
from repro.models.config import ModelConfig
from repro.optim import adamw


@dataclasses.dataclass(frozen=True)
class FLConfig:
    mode: str = "tdm"               # centralized | decentralized | tdm
    local_steps: int = 1            # H: optimizer steps between exchanges
    comm: str = "getmeas"           # getmeas | get1meas (paper primitives)
    compression: str = "none"       # none | int8 | topk
    topk_k: int = 64
    fused: bool = True              # flat-buffer exchange engine (core/fused)


def _stack_init(
    key, cfg: ModelConfig, opt_cfg, n_nodes: int, mesh: Mesh, axis="data"
):
    """Per-node states, stacked on a leading node axis sharded over
    ``mesh``'s ``axis`` (a name, or a tuple of names for a 2D node mesh).

    Every node starts from the SAME init (consensus start: seed is
    ``fold_in(key, 0)`` for all of them), so the model/opt state is built
    once and broadcast — not re-initialized n_nodes times. The stack is
    created already sharded: each device only ever holds its own node.
    """

    def init(key):
        params, _ = registry.bundle(cfg).init(jax.random.fold_in(key, 0))
        state = {
            "params": params,
            "opt": adamw.init_opt_state(params, opt_cfg),
            "step": jnp.zeros((), jnp.int32),
        }
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n_nodes,) + x.shape), state
        )

    return jax.jit(init, out_shardings=NamedSharding(mesh, P(axis)))(key)


def _local_steps(loss_fn, opt_cfg: adamw.OptConfig, state, batch, n_steps: int):
    """``n_steps`` AdamW steps of one node on its own batches (leading axis
    = step). Each step's forward, loss and backward run under the
    ``local_step`` name scope, its clipping and update under ``optimizer``,
    so a profile attributes the round's device time to them by name.
    Returns ``(state, mean loss)``."""
    losses = []
    for h in range(n_steps):
        with jax.named_scope("local_step"):
            mb = jax.tree.map(lambda x: x[h], batch)
            (loss, _), grads = jax.value_and_grad(
                lambda p: loss_fn(p, mb), has_aux=True
            )(state["params"])
        with jax.named_scope("optimizer"):
            new_p, new_opt, _ = adamw.apply_updates(
                state["params"], grads, state["opt"], opt_cfg
            )
            state = {"params": new_p, "opt": new_opt, "step": state["step"] + 1}
        losses.append(loss)
    with jax.named_scope("local_step"):
        return state, jnp.stack(losses).mean()


def _record_step_gauges(cfg: ModelConfig, state, batch, rec) -> None:
    """Gauges of one local step, per node, from one node's abstract shapes:
    the model's own (:meth:`repro.models.registry.ModelBundle.step_gauges`:
    ``attention.kernel_layers``, and a dropless MoE's ``moe.experts_held``,
    ``moe.expert_rows`` and ``moe.expert_rows_first``), then
    ``fl.remat_saved_bytes``, the bytes the forward keeps for its backward
    by remat policy (a Mamba-2 layer's saved in-projections and SSD output;
    0 for a model without Mamba layers). Costs one trace of the loss, so
    it is recorded on a round-cache miss with tracing on only."""
    if not rec.tracing:
        return
    bundle = registry.bundle(cfg)
    for name, value in bundle.step_gauges(*batch["tokens"].shape[2:]).items():
        telemetry.set_gauge(name, value, rec=rec)
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), state["params"]
    )
    step_batch = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape[2:], x.dtype), batch
    )
    saved = flops.remat_saved_bytes(
        lambda p, b: bundle.loss_fn(p, b)[0], params, step_batch
    )
    telemetry.set_gauge("fl.remat_saved_bytes", saved, rec=rec)


def _n_buckets(stacked_params) -> int:
    """Dtype buckets of the fused spec, read from the leaves' dtypes without
    touching device values (no slicing — counters must stay sync-free)."""
    return len({leaf.dtype.name for leaf in jax.tree.leaves(stacked_params)})


def _round_program(
    cfg: ModelConfig,
    opt_cfg: adamw.OptConfig,
    mesh: Mesh,
    axis,
    local_steps: int,
    exchange: Callable,
    *,
    sink_mask: Optional[np.ndarray] = None,
    aux: bool = False,
) -> Callable:
    """The program of every FL round: each node runs ``local_steps`` AdamW
    steps on its own batches, then ``exchange`` under the ``exchange`` name
    scope. Returns a jit'd ``(stacked_state[, aux], stacked_batch) ->
    (stacked_state[, aux], losses)`` whose arguments carry a leading node
    axis sharded over ``axis`` (a mesh axis name, or a tuple of them) and
    whose state (and ``aux``) is donated.

    ``exchange(params[, aux]) -> (params[, aux])`` runs on one node's
    parameters, and on its ``aux`` buffers when ``aux`` is set. Where
    ``sink_mask[node]`` is set, the node keeps its state from before the
    local steps: ground sinks are aggregation infrastructure, not learners
    (their lanes compute and discard, as SPMD demands)."""
    loss_fn = registry.bundle(cfg).loss_fn

    def node_round(*args):
        # every argument's leading dim = 1 (this node's shard); squeeze it
        state, *aux_in, batch = [jax.tree.map(lambda x: x[0], a) for a in args]
        if sink_mask is not None:
            idx = jax.lax.axis_index(axis)
            is_sink = jnp.asarray(sink_mask)[idx]

        trained, local_loss = _local_steps(
            loss_fn, opt_cfg, state, batch, local_steps
        )
        if sink_mask is not None:
            trained = jax.tree.map(
                lambda new, old: jnp.where(is_sink, old, new), trained, state
            )

        with jax.named_scope("exchange"):
            params, *aux_out = exchange(trained["params"], *aux_in)
        out = [dict(trained, params=params), *aux_out]
        return (*(jax.tree.map(lambda x: x[None], o) for o in out), local_loss[None])

    specs = (P(axis),) * (3 if aux else 2)
    fn = jax.shard_map(
        node_round,
        mesh=mesh,
        in_specs=specs,
        out_specs=specs,
        check_vma=False,  # model-internal scans carry node-invariant zeros
                          # (and the pallas int8 path); vma tracking would
                          # demand pcasts throughout
    )
    return jax.jit(fn, donate_argnums=(0, 1) if aux else (0,))


def build_fl_round(
    cfg: ModelConfig,
    opt_cfg: adamw.OptConfig,
    mesh: Mesh,
    n_nodes: int,
    fl_cfg: FLConfig,
    rel: Relation,
    axis: str = "data",
) -> Callable:
    """One FL round = local_steps SGD steps on node-local data + one
    exchange over ``rel``. Returns a jit'd (stacked_state, stacked_batch) ->
    (stacked_state, metrics) function."""
    tdm_cfg = fl.TDMFLAConfig(
        comm=fl_cfg.comm,
        compression=fl_cfg.compression,
        topk_k=fl_cfg.topk_k,
        fused=fl_cfg.fused,
    )

    def exchange(params):
        # ---- the paper's communication step
        if fl_cfg.mode == "centralized":
            params = fl.centralized_round(params, axis)
        elif fl_cfg.mode == "decentralized":
            params = fl.decentralized_round(params, axis, n_nodes)
        else:
            params, _ = fl.tdm_fla_round(params, rel, axis, n_nodes, tdm_cfg)
        return (params,)

    return _round_program(cfg, opt_cfg, mesh, axis, fl_cfg.local_steps, exchange)


def build_hierarchical_fl_round(
    cfg: ModelConfig,
    opt_cfg: adamw.OptConfig,
    mesh: Mesh,
    n_pods: int,
    n_data: int,
    fl_cfg: FLConfig,
    intra_rel: Relation,
    inter_rel: Relation,
    pod_axis: str = "pod",
    data_axis: str = "data",
) -> Callable:
    """One hierarchical (pod × data) FL round: ``local_steps`` SGD steps on
    node-local data, then two-level fused gossip — ``intra_rel`` over the
    data axis inside each pod, ``inter_rel`` over the pod axis across pods
    (:func:`repro.core.fused.fused_hierarchical_round`). ``mesh`` must be a
    2D ``(pod_axis, data_axis)`` mesh of ``n_pods × n_data`` devices; state
    and batches carry a leading node axis sharded over BOTH mesh axes.

    ``fl_cfg.compression`` selects the fused wire format per level:
    ``"none"`` (f32 buffers) or ``"int8"`` (quantize-once blockwise via the
    tdm_compress kernels; 2 permutes per matching per bucket — the
    :func:`repro.telemetry.expected_hierarchical_collectives` oracle).
    Returns a jit'd (stacked_state, stacked_batch) -> (stacked_state,
    losses) function with the :func:`build_fl_round` contract."""
    from repro.core import fused as fused_lib

    if fl_cfg.compression not in ("none", "int8"):
        raise ValueError(
            f"hierarchical FL supports compression 'none'/'int8', "
            f"got {fl_cfg.compression!r}"
        )

    def exchange(params):
        return (
            fused_lib.fused_hierarchical_round(
                params,
                intra_rel,
                inter_rel,
                data_axis,
                pod_axis,
                n_data,
                n_pods,
                compression=fl_cfg.compression,
            ),
        )

    return _round_program(
        cfg, opt_cfg, mesh, (pod_axis, data_axis), fl_cfg.local_steps, exchange
    )


class _ProgramCache:
    """Compiled round programs by key, built on a miss.

    Misses and hits land on the flight recorder as ``<prefix>.misses`` /
    ``<prefix>.hits`` counters, each miss also as a ``retrace`` event of
    ``kind``. With example arguments, a miss records the step's gauges
    (:func:`_record_step_gauges`, tracing on only) and, in reconcile mode,
    is ahead-of-time compiled inside a ``span`` via
    :func:`repro.telemetry.compile_and_check`, so the cached executable is
    the one the collective oracle verified.
    """

    def __init__(self, cfg: ModelConfig, prefix: str, kind: str, span: str):
        self.cfg = cfg
        self.prefix, self.kind, self.span = prefix, kind, span
        self._fns: Dict[Any, Callable] = {}
        self._expected: Dict[Any, Optional[Dict[str, int]]] = {}

    def expected(
        self, key, oracle: Callable[[], Optional[Dict[str, int]]]
    ) -> Optional[Dict[str, int]]:
        """The collective counts ``oracle()`` gives for ``key``'s program,
        memoized on the key (host work only, no device values)."""
        if key not in self._expected:
            self._expected[key] = oracle()
        return self._expected[key]

    def get(
        self,
        key,
        build: Callable[[], Callable],
        example_args: Optional[tuple],
        expected: Callable[[], Optional[Dict[str, int]]],
        *,
        context: str,
        span_args: Dict[str, Any],
        **event_args,
    ) -> Callable:
        """The program of ``key``, made by ``build()`` on a miss.
        ``example_args`` is ``(state, [aux,] batch)``; ``expected()`` gives
        the oracle's collective counts and ``context`` the label for
        ``compile_and_check``; ``span_args`` are the compile span's fields,
        ``event_args`` the ``retrace`` event's."""
        rec = telemetry.get_recorder()
        fn = self._fns.get(key)
        if fn is not None:
            rec.counter(f"{self.prefix}.hits")
            return fn
        rec.counter(f"{self.prefix}.misses")
        rec.event(
            "retrace",
            cat="compile",
            kind=self.kind,
            **event_args,
            cache_size=len(self._fns),
        )
        fn = build()
        if example_args is not None:
            _record_step_gauges(self.cfg, example_args[0], example_args[-1], rec)
            if rec.reconcile:
                with rec.span(self.span, cat="compile", **span_args):
                    fn = telemetry.compile_and_check(
                        fn, example_args, expected(), context=context, recorder=rec
                    )
        self._fns[key] = fn
        return fn

    def __len__(self) -> int:
        return len(self._fns)


class RoundFnCache(_ProgramCache):
    """Compiled FL-round functions keyed by slot relation.

    Time-varying schedules revisit topologies (orbits are periodic), so the
    jit cache is keyed on the relation's pair set — each distinct topology
    compiles once, every revisit is a cache hit. Misses and hits count as
    ``fl.round_cache.*``; in reconcile mode each miss is checked against
    :meth:`expected_collectives` (see :class:`_ProgramCache`).
    """

    def __init__(
        self,
        cfg: ModelConfig,
        opt_cfg,
        mesh: Mesh,
        n_nodes: int,
        fl_cfg: FLConfig,
        axis: str = "data",
    ):
        super().__init__(cfg, "fl.round_cache", "fl_round", "fl.compile")
        self.args = (cfg, opt_cfg, mesh, n_nodes, fl_cfg)
        self.n_nodes = n_nodes
        self.axis = axis

    def expected_collectives(
        self, rel: Relation, state: Any
    ) -> Optional[Dict[str, int]]:
        """Static per-round collective oracle for ``rel``, memoized on the
        cache key. ``None`` when no proven oracle covers the config (only
        the fused getMeas TDM path has one). Mixed-dtype compressed params
        ARE covered: the per-bucket formula is uniform — every dtype
        bucket pays the same sidecar structure (int8 ships payload+scales
        per bucket, fused top-k packs values+indices into one payload per
        bucket), so the count is ``matchings × per × n_buckets``."""
        fl_cfg = self.args[4]

        def oracle():
            if fl_cfg.mode == "tdm" and fl_cfg.fused and fl_cfg.comm == "getmeas":
                return telemetry.expected_tdm_collectives(
                    rel, _n_buckets(state["params"]), compression=fl_cfg.compression
                )
            return None

        return self.expected(tuple(sorted(rel.pairs)), oracle)

    def __call__(self, rel: Relation, example_args=None) -> Callable:
        links = len(rel) // 2
        return self.get(
            tuple(sorted(rel.pairs)),
            lambda: build_fl_round(*self.args, rel, axis=self.axis),
            example_args,
            lambda: self.expected_collectives(rel, example_args[0]),
            context=f"fl_round[{links} links]",
            span_args={"links": links},
            links=links,
        )


@dataclasses.dataclass(frozen=True)
class RoundLog:
    round: int
    loss: float
    consensus: float
    n_links: int        # undirected ISLs active this round
    alive: int          # participating satellites


def run_tdm_rounds(
    cache: RoundFnCache,
    state: Any,
    relations: Sequence[Relation],
    batch_fn: Callable[[int], Any],
    alive: Optional[set] = None,
    on_round: Optional[Callable[[RoundLog], None]] = None,
    log_every: int = 1,
):
    """Drive one FL round per slot relation (the time-varying-schedule mode).

    ``alive`` is read *each round*, so callers may mutate it mid-flight to
    model satellite failures; occluded/dead nodes drop out of the round's
    relation via ``Relation.restrict`` (paper skip-slot semantics) while
    their local training continues. Returns (state, [RoundLog, ...]).

    ``log_every``: compute loss/consensus metrics only every k-th round
    (always including round 0). ``consensus_distance`` transfers the full
    stacked parameters to the host — a device sync per round that benchmark
    and long runs don't want; skipped rounds log NaN metrics and never touch
    device values, so rounds stay async-dispatchable. ``log_every=0``
    disables metrics entirely.

    Telemetry: every round bumps default-on flight-recorder counters
    (``fl.rounds``, cache hit/miss, the oracle's per-round collective
    counts) — host-side dict updates only, no extra device syncs. With
    tracing on, each compiled round also sets the step's gauges
    (``fl.remat_saved_bytes``, ``moe.*``) from abstract shapes. Each
    round runs inside an ``fl.round`` span, which a profiler session sees
    on the host plane and which the recorder keeps (``cat="slot"``) with
    tracing on. The span times the host's dispatch of the round, never the
    device's work: nothing waits for the device, traced or not, so rounds
    stay async-dispatchable. Per-round device time is read from the
    profiler's device plane.
    """
    rec = telemetry.get_recorder()
    n_nodes = cache.n_nodes
    logs = []
    for rnd, rel in enumerate(relations):
        live = set(alive) if alive is not None else set(range(n_nodes))
        rel_t = rel.restrict(live)
        batch = batch_fn(rnd)
        with rec.span(
            "fl.round",
            cat="slot",
            round=rnd,
            links=len(rel_t) // 2,
            alive=len(live),
        ):
            fn = cache(
                rel_t,
                example_args=(
                    (state, batch) if rec.reconcile or rec.tracing else None
                ),
            )
            state, losses = fn(state, batch)
        rec.counter("fl.rounds")
        expected = cache.expected_collectives(rel_t, state)
        if expected:
            for kind, count in expected.items():
                rec.counter(f"fl.collectives.{kind}", count)
        log_this = log_every > 0 and rnd % log_every == 0
        log = RoundLog(
            round=rnd,
            loss=float(jnp.mean(losses)) if log_this else float("nan"),
            consensus=(
                consensus_distance(state["params"]) if log_this else float("nan")
            ),
            n_links=len(rel_t) // 2,
            alive=len(live),
        )
        logs.append(log)
        if on_round is not None:
            on_round(log)
    return state, logs


def run_constellation_fl(
    cfg: ModelConfig,
    opt_cfg,
    mesh: Mesh,
    n_nodes: int,
    fl_cfg: FLConfig,
    plan,
    state: Any,
    batch_fn: Callable[[int], Any],
    rounds: Optional[int] = None,
    alive: Optional[set] = None,
    on_round: Optional[Callable[[RoundLog], None]] = None,
    optimize: Optional[str] = None,
    antennas=None,
    payload_bytes: int = 1 << 20,
    acquisition_s: float = 0.0,
    log_every: int = 1,
):
    """Constellation-driven FL: one round per contact-plan time step.

    ``plan`` is a :class:`repro.constellation.contact_plan.ContactPlan`;
    its geometry-derived visibility relations *are* the TDM schedule. When
    ``rounds`` exceeds the plan horizon the plan repeats (orbits are
    periodic when the horizon is one period).

    ``optimize`` switches the round schedule from the raw per-step
    visibility relations to a materialized antenna-constrained
    ``ContactSchedule`` — ``"greedy"`` for the first-legal-coloring
    baseline, ``"rate"`` for the min-cost schedule over the optimizer's
    strategy portfolio for this plan window (never costlier than greedy;
    see :mod:`repro.constellation.optimizer`). One FL round then runs per
    emitted sub-slot. ``antennas``/``payload_bytes``/``acquisition_s`` are
    the physical knobs the schedule is sized (and priced) with; with zero
    slew penalty and an antenna budget covering each step's degree, greedy
    and rate-aware emit the identical relation sequence, so training is
    bit-for-bit unchanged — only the time accounting improves.

    The schedule is built for the full constellation; ``alive`` keeps its
    ``run_tdm_rounds`` contract (read each round, mutable mid-flight), so
    failures and recoveries apply per round in both modes. A plan window
    with no feasible contacts falls back to the per-step relations (all
    empty), preserving the skip-slot semantics: local training continues.
    """
    if optimize is None:
        relations = plan.relations()
    else:
        with telemetry.get_recorder().span(
            "fl.build_schedule", cat="schedule", optimize=optimize
        ):
            sched = plan.schedule(
                antennas=antennas,
                payload_bytes=payload_bytes,
                optimize=optimize,
                acquisition_s=acquisition_s,
            )
        relations = list(sched.tdm)
        if not relations:
            relations = plan.relations()
    if rounds is not None:
        reps = -(-rounds // max(len(relations), 1))
        relations = (relations * reps)[:rounds]
    cache = RoundFnCache(cfg, opt_cfg, mesh, n_nodes, fl_cfg)
    return run_tdm_rounds(
        cache, state, relations, batch_fn, alive, on_round, log_every=log_every
    )


# ===========================================================================
# Ground-segment (centralized / hierarchical) FL over contact-graph routes
# ===========================================================================

@dataclasses.dataclass(frozen=True)
class GroundSegConfig:
    """Config for sink-based FL over the ground segment.

    mode: 'centralized'  — sinks pool every round over terrestrial backhaul
                           (one masked psum per buffer); every satellite
                           that the downlink reaches gets the same global.
          'hierarchical' — sinks keep regional FedAvg models and pool only
                           every ``sink_sync_every`` rounds; regions mix on
                           the sync cadence (and through satellites whose
                           routes migrate between sinks as orbits advance).
    compression: relay payload encoding ('none' | 'int8' — blockwise via
                 the tdm_compress kernels, quantized ONCE end-to-end:
                 pmax-shared scales, exact int16 relay sums on the wire,
                 single dequant at the sink).
    pipeline_depth: 1 — one-shot rounds: uplink then downlink traverse the
                    window sequentially (the PR 4 path, bit-for-bit when
                    ``max_staleness_windows == 0``). 2 — pipelined: round
                    r's downlink flood overlaps round r+1's uplink relay
                    inside ONE window, on disjoint slot capacity — the
                    sink never idles and steady-state round throughput
                    roughly doubles.
    max_staleness_windows: delay-tolerant horizon — an undelivered payload
                    persists (and keeps aging) this many windows before it
                    is dropped and reported; 0 disables persistence.
    staleness_decay: sink FedAvg weight of a payload delivered at age
                    ``a`` is ``staleness_decay ** a`` (1.0 = pure FedAvg
                    regardless of age; age 0 is always weight 1 — exact
                    FedAvg recovered when nothing is stale).
    """

    mode: str = "centralized"
    sink_sync_every: int = 2
    compression: str = "none"
    block: int = 1024
    quant_impl: str = "auto"
    pipeline_depth: int = 1
    max_staleness_windows: int = 0
    staleness_decay: float = 0.5

    def __post_init__(self):
        if self.mode not in ("centralized", "hierarchical"):
            raise ValueError(f"unknown groundseg mode {self.mode!r}")
        if self.compression not in ("none", "int8"):
            raise ValueError(
                f"groundseg compression must be 'none' or 'int8', "
                f"got {self.compression!r}"
            )
        if self.pipeline_depth not in (1, 2):
            raise ValueError(
                f"pipeline_depth must be 1 or 2, got {self.pipeline_depth}"
            )
        if self.max_staleness_windows < 0:
            raise ValueError(
                f"max_staleness_windows must be >= 0, "
                f"got {self.max_staleness_windows}"
            )
        if not (0.0 < self.staleness_decay <= 1.0):
            raise ValueError(
                f"staleness_decay must be in (0, 1], got {self.staleness_decay}"
            )

    @property
    def pipelined(self) -> bool:
        """Does this config need the multi-window engine? The trivial
        config (depth 1, no persistence) routes through the PR 4 one-shot
        path, whose numerics the pipelined engine reproduces bit-for-bit
        (HLO-verified in tests/_groundseg_worker.py)."""
        return self.pipeline_depth > 1 or self.max_staleness_windows > 0

    def pool_round(self, rnd: int) -> bool:
        """Do the sinks reconcile over backhaul this round?"""
        if self.mode == "centralized":
            return True
        return self.sink_sync_every > 0 and rnd % self.sink_sync_every == 0


def build_groundseg_round(
    cfg: ModelConfig,
    opt_cfg: adamw.OptConfig,
    mesh: Mesh,
    n_nodes: int,
    fl_cfg: FLConfig,
    gs_cfg: GroundSegConfig,
    uplink,
    downlink,
    pool: bool,
    axis: str = "data",
) -> Callable:
    """One ground-segment FL round: satellites run ``local_steps`` SGD
    steps on their own shards (sinks hold — ground stations have no
    training data, their lanes compute and discard, as SPMD demands), then
    the full uplink-relay -> sink-FedAvg -> downlink-broadcast exchange
    from :func:`repro.groundseg.aggregation.groundseg_round` runs on the
    fused buffers. Same (stacked_state, stacked_batch) contract as
    :func:`build_fl_round`."""
    from repro.groundseg import aggregation

    def exchange(params):
        return (
            aggregation.groundseg_round(
                params,
                uplink,
                downlink,
                axis,
                pool=pool,
                compression=gs_cfg.compression,
                block=gs_cfg.block,
                quant_impl=gs_cfg.quant_impl,
            ),
        )

    return _round_program(
        cfg, opt_cfg, mesh, axis, fl_cfg.local_steps, exchange,
        sink_mask=np.isin(np.arange(n_nodes), sorted(uplink.sinks)),
    )


def build_pipelined_groundseg_round(
    cfg: ModelConfig,
    opt_cfg: adamw.OptConfig,
    mesh: Mesh,
    n_nodes: int,
    fl_cfg: FLConfig,
    gs_cfg: GroundSegConfig,
    wp,
    pool: bool,
    axis: str = "data",
) -> Callable:
    """One pipelined/delay-tolerant window: local training (sinks hold),
    then :func:`repro.groundseg.aggregation.pipelined_window_round` on the
    fused buffers. Contract: ``(stacked_state, aux, stacked_batch) ->
    (stacked_state, aux, losses)`` where ``aux = {"carry": .., "pending":
    ..}`` are the stacked payload-queue and pending-global buffer dicts
    threaded across windows."""
    from repro.groundseg import aggregation

    def exchange(params, aux):
        params, carry, pending = aggregation.pipelined_window_round(
            params,
            aux["carry"],
            aux["pending"],
            wp,
            axis,
            pool=pool,
            staleness_decay=gs_cfg.staleness_decay,
            compression=gs_cfg.compression,
            block=gs_cfg.block,
            quant_impl=gs_cfg.quant_impl,
        )
        return params, {"carry": carry, "pending": pending}

    return _round_program(
        cfg, opt_cfg, mesh, axis, fl_cfg.local_steps, exchange,
        sink_mask=np.isin(np.arange(n_nodes), sorted(wp.uplink.sinks)),
        aux=True,
    )


@dataclasses.dataclass(frozen=True)
class GroundSegRoundLog:
    round: int
    loss: float          # mean over live satellites (sinks excluded)
    consensus: float     # consensus distance over satellite params
    delivered: int       # satellite payloads landing at sinks this round
    covered: int         # satellites the downlink reached
    unreachable: int     # live satellites with no route to any sink
    alive: int           # live satellites
    pooled: bool         # sinks reconciled over backhaul this round
    carried: int = 0     # payloads persisting to the next window
    dropped: int = 0     # payloads discarded past the staleness horizon
    max_age: int = 0     # oldest delivered payload's age (windows)


def run_groundseg_fl(
    cfg: ModelConfig,
    opt_cfg,
    mesh: Mesh,
    n_nodes: int,
    fl_cfg: FLConfig,
    gs_cfg: GroundSegConfig,
    plan,
    state: Any,
    batch_fn: Callable[[int], Any],
    sinks,
    rounds: int,
    alive: Optional[set] = None,
    on_round: Optional[Callable[[GroundSegRoundLog], None]] = None,
    optimize: Optional[str] = None,
    antennas=None,
    payload_bytes: int = 1 << 20,
    acquisition_s: float = 0.0,
    log_every: int = 1,
):
    """Centralized/hierarchical FL with ground stations as aggregation
    sinks, routed over the plan's materialized TDM schedule.

    ``plan`` must include the ground stations
    (``build_contact_plan(..., ground_stations=[...])``); ``sinks`` are
    their node ids (satellites first, then ground — node ids ``geom.total``
    onward). Each round: local training, store-and-forward uplink of every
    reachable satellite's params along its earliest-delivery route, sink
    FedAvg (pooled per :meth:`GroundSegConfig.pool_round`), and the global
    (or regional) model flooding back on the downlink — uplink on one
    schedule window, downlink on the next identical window (orbits are
    periodic when the horizon is one period).

    ``alive`` keeps the :func:`run_tdm_rounds` contract: read every round,
    mutable mid-flight; sinks are ground infrastructure and always up.
    Routing, relay and broadcast programs, and the compiled round are
    cached per (alive-set, pool-flag) — orbital periodicity makes revisits
    cache hits. Returns ``(state, [GroundSegRoundLog, ...])``.

    When ``gs_cfg.pipelined`` (``pipeline_depth == 2`` and/or
    ``max_staleness_windows > 0``) the multi-window engine drives the loop
    instead: a :class:`repro.groundseg.routing.MultiWindowRouter` re-plans
    each window from the live set, undelivered payloads persist in a carry
    buffer across windows (dropped and reported past the staleness
    horizon), and at depth 2 round r's downlink overlaps round r+1's
    uplink on disjoint slot capacity. The compiled-window cache is keyed by
    (alive set, payload ages, pool, downlink presence) — steady state
    revisits the same few keys.
    """
    from repro.groundseg import routing

    sinks_s = frozenset(int(s) for s in sinks)
    if not sinks_s:
        raise ValueError("run_groundseg_fl needs at least one sink node id")
    sched = plan.schedule(
        antennas=antennas,
        payload_bytes=payload_bytes,
        optimize=optimize,
        acquisition_s=acquisition_s,
    )
    base_rels = list(sched.tdm)
    sat_ids = [v for v in range(n_nodes) if v not in sinks_s]
    if gs_cfg.pipelined:
        return _run_groundseg_pipelined(
            cfg, opt_cfg, mesh, n_nodes, fl_cfg, gs_cfg, base_rels, state,
            batch_fn, sinks_s, sat_ids, rounds, alive, on_round, log_every,
        )
    # routing depends only on the alive set; the compiled round also on the
    # pool flag — two caches so hierarchical pool/regional alternation does
    # not redo the DP and program replay
    from repro.groundseg import aggregation

    rec = telemetry.get_recorder()
    n_buckets = _n_buckets(state["params"])
    prog_cache: Dict[Any, Any] = {}
    fn_cache = _ProgramCache(
        cfg, "groundseg.round_cache", "groundseg_round", "groundseg.compile"
    )
    logs: list = []
    for rnd in range(rounds):
        live = set(alive) if alive is not None else set(range(n_nodes))
        live |= sinks_s
        pool = gs_cfg.pool_round(rnd)
        live_key = frozenset(live)
        if live_key not in prog_cache:
            rec.counter("groundseg.route_cache.misses")
            rec.event(
                "reroute", cat="routing", round=rnd, alive=len(live)
            )
            with rec.span("groundseg.route", cat="routing", alive=len(live)):
                rels = [r.restrict(live) for r in base_rels]
                table = routing.earliest_delivery_routes(
                    rels,
                    n_nodes,
                    sinks_s,
                    sources=[v for v in sat_ids if v in live],
                )
                up = routing.build_relay_program(
                    rels, n_nodes, sinks_s, table=table
                )
                down = routing.build_broadcast_program(rels, n_nodes, sinks_s)
            prog_cache[live_key] = (up, down)
        else:
            rec.counter("groundseg.route_cache.hits")
        up, down = prog_cache[live_key]
        fn_key = (live_key, pool)
        expected = fn_cache.expected(
            fn_key,
            lambda: aggregation.expected_collectives(
                up, down, n_buckets, compression=gs_cfg.compression, pool=pool
            ),
        )
        batch = batch_fn(rnd)
        fn = fn_cache.get(
            fn_key,
            lambda: build_groundseg_round(
                cfg, opt_cfg, mesh, n_nodes, fl_cfg, gs_cfg, up, down, pool
            ),
            (state, batch),
            lambda: expected,
            context=f"groundseg_round[pool={pool}]",
            span_args={"pool": pool},
            round=rnd,
            pool=pool,
        )
        with rec.span(
            "groundseg.round",
            cat="window",
            round=rnd,
            pool=pool,
            alive=len(live),
            delivered=up.delivered_count(),
            unreachable=len(up.unreachable),
        ):
            state, losses = fn(state, batch)
        rec.counter("groundseg.rounds")
        rec.counter("groundseg.payloads.delivered", up.delivered_count())
        rec.counter("groundseg.payloads.unreachable", len(up.unreachable))
        for kind, count in expected.items():
            rec.counter(f"groundseg.collectives.{kind}", count)
        live_sats = [v for v in sat_ids if v in live]
        loss_v, cons_v = _live_log_values(
            losses, state["params"], live_sats, log_every > 0 and rnd % log_every == 0
        )
        log = GroundSegRoundLog(
            round=rnd,
            loss=loss_v,
            consensus=cons_v,
            delivered=up.delivered_count(),
            covered=len(down.covered - sinks_s),
            unreachable=len(up.unreachable),
            alive=len(live_sats),
            pooled=pool,
        )
        logs.append(log)
        if on_round is not None:
            on_round(log)
    return state, logs


def _run_groundseg_pipelined(
    cfg: ModelConfig,
    opt_cfg,
    mesh: Mesh,
    n_nodes: int,
    fl_cfg: FLConfig,
    gs_cfg: GroundSegConfig,
    base_rels,
    state: Any,
    batch_fn: Callable[[int], Any],
    sinks_s,
    sat_ids,
    rounds: int,
    alive: Optional[set],
    on_round: Optional[Callable[[GroundSegRoundLog], None]],
    log_every: int,
):
    """The multi-window loop behind :func:`run_groundseg_fl`: one window
    per round, payload queues persisting in device-side carry buffers, the
    previous round's global staged in a pending buffer when pipelining."""
    from repro.core import fused
    from repro.groundseg import aggregation, routing

    rec = telemetry.get_recorder()
    router = routing.MultiWindowRouter(
        n_nodes,
        sinks_s,
        max_staleness_windows=gs_cfg.max_staleness_windows,
        pipeline_depth=gs_cfg.pipeline_depth,
    )
    node_params = jax.tree.map(lambda x: x[0], state["params"])
    spec = fused.cached_spec(node_params, block=gs_cfg.block)
    n_buckets = len(spec.buckets)
    aux = {
        "carry": aggregation.stacked_zero_buffers(spec, n_nodes),
        "pending": aggregation.stacked_zero_buffers(spec, n_nodes),
    }
    fn_cache = _ProgramCache(
        cfg, "groundseg.window_cache", "groundseg_window", "groundseg.compile"
    )
    logs: list = []
    for rnd in range(rounds):
        live = set(alive) if alive is not None else set(range(n_nodes))
        live |= sinks_s
        pool = gs_cfg.pool_round(rnd)
        with rec.span("groundseg.plan_window", cat="routing", window=rnd):
            wp = router.plan_window(base_rels, alive=live)
        key = (
            frozenset(live),
            tuple(sorted(wp.ages.items())),
            pool,
            wp.downlink is None,
        )
        expected = fn_cache.expected(
            key,
            lambda: aggregation.expected_window_collectives(
                wp, n_buckets, compression=gs_cfg.compression, pool=pool
            ),
        )
        batch = batch_fn(rnd)
        fn = fn_cache.get(
            key,
            lambda: build_pipelined_groundseg_round(
                cfg, opt_cfg, mesh, n_nodes, fl_cfg, gs_cfg, wp, pool
            ),
            (state, aux, batch),
            lambda: expected,
            context=f"groundseg_window[{wp.window}, pool={pool}]",
            span_args={"pool": pool},
            window=wp.window,
            pool=pool,
            ages=dict(wp.ages),
        )
        with rec.span(
            "groundseg.window",
            cat="window",
            window=wp.window,
            pool=pool,
            alive=len(live),
            queued=len(wp.injected),
            delivered=wp.uplink.delivered_count(),
            carried=len(wp.residual),
            dropped=len(wp.dropped),
        ):
            state, aux, losses = fn(state, aux, batch)
        # payload lifecycle: queued -> relayed -> delivered | carried |
        # dropped. Counters are default-on; per-payload instants (with
        # staleness ages) exist only while tracing.
        rec.counter("groundseg.rounds")
        rec.counter("groundseg.payloads.queued", len(wp.injected))
        rec.counter("groundseg.payloads.delivered", wp.uplink.delivered_count())
        rec.counter("groundseg.payloads.carried", len(wp.residual))
        rec.counter("groundseg.payloads.dropped", len(wp.dropped))
        rec.counter("groundseg.payloads.unreachable", len(wp.uplink.unreachable))
        rec.set_counter(
            "groundseg.payloads.max_delivered_age",
            max(
                rec.get_counter("groundseg.payloads.max_delivered_age"),
                wp.max_delivered_age(),
            ),
        )
        for kind, count in expected.items():
            rec.counter(f"groundseg.collectives.{kind}", count)
        if rec.tracing:
            for src in sorted(wp.injected):
                rec.event(
                    "payload.queued", cat="payload", window=wp.window, source=src
                )
            for name, ages in (
                ("payload.delivered", wp.delivered_ages),
                ("payload.carried", wp.residual),
                ("payload.dropped", wp.dropped),
            ):
                for src, age in sorted(ages.items()):
                    rec.event(
                        name, cat="payload", window=wp.window, source=src, age=age
                    )
        live_sats = [v for v in sat_ids if v in live]
        loss_v, cons_v = _live_log_values(
            losses, state["params"], live_sats, log_every > 0 and rnd % log_every == 0
        )
        log = GroundSegRoundLog(
            round=rnd,
            loss=loss_v,
            consensus=cons_v,
            delivered=wp.uplink.delivered_count(),
            covered=(
                0 if wp.downlink is None else len(wp.downlink.covered - sinks_s)
            ),
            unreachable=len(wp.uplink.unreachable),
            alive=len(live_sats),
            pooled=pool,
            carried=len(wp.residual),
            dropped=len(wp.dropped),
            max_age=wp.max_delivered_age(),
        )
        logs.append(log)
        if on_round is not None:
            on_round(log)
    return state, logs


def _live_log_values(losses, stacked_params, live_sats, log_this: bool):
    """A ground-segment round's logged ``(loss, consensus)``: the mean loss
    and :func:`consensus_distance` over the live satellites ``live_sats``,
    read back from the device; NaN for both, reading nothing, on a round
    not logged or with no live satellite."""
    if not (log_this and live_sats):
        return float("nan"), float("nan")
    return float(np.mean(np.asarray(losses)[live_sats])), consensus_distance(
        jax.tree.map(lambda x: np.asarray(x)[live_sats], stacked_params)
    )


def consensus_distance(stacked_params) -> float:
    """Max relative L2 distance of any node's params from the mean."""
    leaves = jax.tree.leaves(stacked_params)
    num = 0.0
    den = 0.0
    for leaf in leaves:
        arr = np.asarray(leaf, dtype=np.float64)
        mean = arr.mean(axis=0, keepdims=True)
        num += float(np.square(arr - mean).sum())
        den += float(np.square(mean).sum() * arr.shape[0])
    return (num / max(den, 1e-30)) ** 0.5


# ---------------------------------------------------------------------------
# One driver entry point (ISSUE 10): run(cfg) dispatches on config type
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TDMRun:
    """Config for :func:`run_tdm_rounds` — one FL round per slot relation."""

    cache: RoundFnCache
    state: Any
    relations: Sequence[Relation]
    batch_fn: Callable[[int], Any]
    alive: Optional[set] = None
    on_round: Optional[Callable[[RoundLog], None]] = None
    log_every: int = 1


@dataclasses.dataclass
class ConstellationRun:
    """Config for :func:`run_constellation_fl` — geometry-driven rounds."""

    cfg: ModelConfig
    opt_cfg: Any
    mesh: Mesh
    n_nodes: int
    fl_cfg: FLConfig
    plan: Any
    state: Any
    batch_fn: Callable[[int], Any]
    rounds: Optional[int] = None
    alive: Optional[set] = None
    on_round: Optional[Callable[[RoundLog], None]] = None
    optimize: Optional[str] = None
    antennas: Any = None
    payload_bytes: int = 1 << 20
    acquisition_s: float = 0.0
    log_every: int = 1


@dataclasses.dataclass
class GroundSegRun:
    """Config for :func:`run_groundseg_fl` — ground stations as sinks."""

    cfg: ModelConfig
    opt_cfg: Any
    mesh: Mesh
    n_nodes: int
    fl_cfg: FLConfig
    gs_cfg: GroundSegConfig
    plan: Any
    state: Any
    batch_fn: Callable[[int], Any]
    sinks: Any = ()
    rounds: int = 1
    alive: Optional[set] = None
    on_round: Optional[Callable[[GroundSegRoundLog], None]] = None
    optimize: Optional[str] = None
    antennas: Any = None
    payload_bytes: int = 1 << 20
    acquisition_s: float = 0.0
    log_every: int = 1


@dataclasses.dataclass
class RunResult:
    """Shared return shape of :func:`run`: mode tag + final state + logs."""

    mode: str                    # "tdm" | "constellation" | "groundseg"
    state: Any
    logs: List[Any]

    @property
    def n_rounds(self) -> int:
        return len(self.logs)

    @property
    def final(self) -> Any:
        """Last round's log (None for a zero-round run)."""
        return self.logs[-1] if self.logs else None


# config type -> (mode tag, the function its fields are the arguments of)
_RUNS = {
    TDMRun: ("tdm", run_tdm_rounds),
    ConstellationRun: ("constellation", run_constellation_fl),
    GroundSegRun: ("groundseg", run_groundseg_fl),
}


def run(run_cfg) -> RunResult:
    """One driver entry point over the three FL modes.

    Dispatches on the config dataclass type — :class:`TDMRun` →
    :func:`run_tdm_rounds`, :class:`ConstellationRun` →
    :func:`run_constellation_fl`, :class:`GroundSegRun` →
    :func:`run_groundseg_fl` — calling the function with the config's
    fields as keyword arguments, and normalizes the ``(state, logs)``
    returns into one :class:`RunResult`. The underlying functions are
    unchanged (and remain directly callable); this is pure plumbing so
    examples and higher drivers can switch modes by swapping a config
    object. The fields are passed as they are, not copied: a caller may
    still mutate ``alive`` between rounds.
    """
    for cls, (mode, fn) in _RUNS.items():
        if isinstance(run_cfg, cls):
            fields = dataclasses.fields(run_cfg)
            state, logs = fn(**{f.name: getattr(run_cfg, f.name) for f in fields})
            return RunResult(mode, state, logs)
    raise TypeError(
        f"run() takes a TDMRun / ConstellationRun / GroundSegRun config, "
        f"got {type(run_cfg).__name__}"
    )
