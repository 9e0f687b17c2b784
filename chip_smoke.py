"""Smoke run of the main path on TPU chips — a check that the program runs,
not a benchmark.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips, one FL node per chip

One chip, at the published widths of ``mamba2-780m`` (random weights from a
seed), in this order:

1. exchange kernels — one node's fused parameter buffer through the Pallas
   ``tdm_compress`` kernels (quantize -> dequant-accumulate, shared-scale
   quantize, top-k -> scatter-accumulate), each compared on the chip with
   the jnp oracle in ``kernels/tdm_compress/ref.py``;
2. FL node — ``fl_train.run`` over a one-device mesh: a few rounds of two
   local AdamW steps; every loss finite and the parameters moved;
3. serving — ``ServingEngine`` over the smoke constellation with one
   ``ModelDecoder`` replica answers a few requests; all delivered, the
   route-provenance audit passes, and the first request's greedy tokens
   equal a plain prefill/decode loop on the same parameters.

``--chips 4`` runs only the four-chip path: four satellite nodes, one per
chip, take an int8 TDM round through ``fl_train.build_fl_round``; the fused
exchange (int8 / top-k / none) of their trained parameters is compared
with the same exchange on the jnp oracle, and for int8/none with a host
numpy Metropolis mix.

Exits nonzero when JAX finds no TPU, and on any failed check. The last
line of stdout is ``{"ok": true, "device": {...}}``. The compile cache is
kept where ``JAX_COMPILATION_CACHE_DIR`` says, else in ``.jax_cache/``
next to this file.
"""

from __future__ import annotations

import argparse
import functools
import json
import math
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import archs  # noqa: E402
from repro.constellation.scenario import smoke_scenario  # noqa: E402
from repro.core import fl, fused, gossip  # noqa: E402
from repro.core.relation import Relation  # noqa: E402
from repro.data import pipeline  # noqa: E402
from repro.kernels.tdm_compress import ref as q_ref  # noqa: E402
from repro.kernels.tdm_compress import tdm_compress as q_kernel  # noqa: E402
from repro.launch import fl_train  # noqa: E402
from repro.launch import mesh as mesh_lib  # noqa: E402
from repro.models import registry  # noqa: E402
from repro.models.config import ShapeConfig  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.serving import (  # noqa: E402
    ModelDecoder,
    ReplicaFleet,
    ServingEngine,
    audit_serving_run,
    synthesize_workload,
)

MODEL = "mamba2-780m"
SEED = 0
BLOCK = fused.DEFAULT_BLOCK
TOPK = 8                  # per-block budget of the top-k kernel check
RTOL = ATOL = 1e-6        # accumulations: the tests/test_kernels.py tolerance
REF_CHUNK_BLOCKS = 65536  # top-k oracle (an argsort) runs chunk by chunk
BATCH, SEQ = 2, 512       # per node, per local step
LOCAL_STEPS = 2
FL_ROUNDS = 2
N_REQUESTS, MAX_NEW, MAX_LEN = 4, 8, 32
GB = float(1 << 30)


def model_config():
    return archs.get(MODEL)


_T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"[smoke +{time.perf_counter() - _T0:.1f}s] {msg}", flush=True)


def check(ok, what: str) -> None:
    log(f"check {what}: {'ok' if ok else 'FAILED'}")
    if not ok:
        raise SystemExit(f"chip_smoke: check failed: {what}")


def require_tpu(n_chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(
            f"chip_smoke: no TPU found (JAX platform {devs[0].platform!r}); "
            "this smoke run needs a TPU chip",
            file=sys.stderr,
        )
        raise SystemExit(2)
    if len(devs) < n_chips:
        print(
            f"chip_smoke: --chips {n_chips} needs {n_chips} TPU chips, "
            f"JAX sees {len(devs)}",
            file=sys.stderr,
        )
        raise SystemExit(2)
    return devs


def use_compile_cache() -> None:
    """JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; only without it is a
    fixed directory in the checkout set (a path that moves never hits)."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))


@jax.jit
def _mismatches(a, b):
    return jnp.sum(a != b)


@jax.jit
def _outside_tol(a, b):
    return jnp.sum(jnp.abs(a - b) > ATOL + RTOL * jnp.abs(b))


def _exact(a, b, what):
    check(a.shape == b.shape and int(_mismatches(a, b)) == 0, f"{what} equal")


def _close(a, b, what):
    check(a.shape == b.shape and int(_outside_tol(a, b)) == 0,
          f"{what} within rtol=atol={RTOL:g}")


def _chip_gib(devices, key: str):
    return [d.memory_stats()[key] / GB for d in devices]


def _compile(fn, *args, kernel: str = ""):
    """Compile a jitted ``fn`` for ``args`` once; when ``kernel`` names the
    program, check that it carries a Pallas kernel (``tpu_custom_call``)."""
    compiled = fn.lower(*args).compile()
    if kernel:
        check("tpu_custom_call" in compiled.as_text(),
              f"{kernel} compiles to a tpu_custom_call")
    return compiled


def compile_and_time(fn, *args, kernel: str = ""):
    """Compile ``fn`` once and run it twice: (second result, compile s,
    second call's wall s)."""
    t0 = time.perf_counter()
    compiled = _compile(jax.jit(fn), *args, kernel=kernel)
    t1 = time.perf_counter()
    jax.block_until_ready(compiled(*args))
    t2 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return out, t1 - t0, time.perf_counter() - t2


# ---------------------------------------------------------------------------
# phase 1: exchange kernels on one node's fused buffer
# ---------------------------------------------------------------------------

def phase_kernels(cfg):
    impl = fused._resolve_impl("auto")
    check(impl != "ref", f"exchange kernel path resolves to Pallas ({impl})")
    interpret = impl == "pallas_interpret"
    params = jax.jit(lambda k: registry.bundle(cfg).init(k)[0])(
        jax.random.PRNGKey(SEED)
    )
    spec = fused.build_spec(params, block=BLOCK)
    (bucket, n), = spec.bucket_sizes
    buf = jax.jit(lambda p: fused.flatten_pytree(spec, p)[bucket])(params)
    del params
    nb = n // BLOCK
    k_fl = max(1, min(BLOCK, -(-64 * spec.n_leaves(bucket) // nb)))
    log(f"fused {bucket} buffer: {n} elements, {spec.n_leaves(bucket)} "
        f"leaves, {nb} blocks of {BLOCK}; FL top-k budget {k_fl}/block, "
        f"kernel check at {TOPK}/block")

    kern = functools.partial
    times = {}

    def run(name, fn, *args):
        out, c, times[name] = compile_and_time(
            kern(fn, block=BLOCK, interpret=interpret), *args,
            kernel="" if interpret else name,
        )
        log(f"{name}: compiled in {c:.1f} s")
        return out

    def oracle(fn, *args, **kw):
        return jax.jit(kern(fn, block=BLOCK, **kw))(*args)

    q, s = run("quantize", q_kernel.quantize_fwd, buf)
    q_r, s_r = oracle(q_ref.quantize_ref, buf)
    _exact(q, q_r, "quantize int8 payload vs ref")
    _exact(s, s_r, "quantize scales vs ref")
    del q_r, s_r

    w_acc = jnp.float32(0.5)
    out = run("dequant_accumulate", q_kernel.dequant_accumulate_fwd, q, s, buf, w_acc)
    _close(out, oracle(q_ref.dequant_acc_ref, q, s, buf, w_acc),
           "dequant-accumulate vs ref")
    del out, q

    shared = s * jnp.float32(1.5)  # pmax-style shared scales >= local ones
    qs = run("quantize_scaled", q_kernel.quantize_scaled_fwd, buf, shared)
    _exact(qs, oracle(q_ref.quantize_scaled_ref, buf, shared),
           "quantize-scaled vs ref")
    del qs, shared, s

    dense, vals, idxs = run(
        "topk_sparsify", kern(q_kernel.topk_sparsify_fwd, k=TOPK), buf
    )
    ref_topk = jax.jit(kern(q_ref.topk_sparsify_ref, k=TOPK, block=BLOCK))
    bad = 0
    for lo in range(0, nb, REF_CHUNK_BLOCKS):
        hi = min(nb, lo + REF_CHUNK_BLOCKS)
        d_r, v_r, i_r = ref_topk(buf[lo * BLOCK:hi * BLOCK])
        bad += int(_mismatches(dense[lo * BLOCK:hi * BLOCK], d_r))
        bad += int(_mismatches(vals[lo:hi], v_r))
        bad += int(_mismatches(idxs[lo:hi], i_r))
        del d_r, v_r, i_r
    check(bad == 0, "top-k dense, values and indices equal ref")
    del dense

    w_sc = jnp.float32(-0.25)
    out = run("scatter_accumulate", q_kernel.scatter_accumulate_fwd,
              vals, idxs, buf, w_sc)
    _close(out, oracle(q_ref.scatter_acc_ref, vals, idxs, buf, w_sc),
           "scatter-accumulate vs ref")
    del out, vals, idxs, buf
    for name, t in times.items():
        log(f"kernel {name}: {t * 1e3:.3f} ms per call after warm-up "
            f"({n * 4 / t / 1e9:.1f} GB/s of f32 buffer)")


# ---------------------------------------------------------------------------
# phase 2: one FL node
# ---------------------------------------------------------------------------

def make_batch_fn(cfg, n_nodes: int):
    shape = ShapeConfig("smoke", "train", SEQ, BATCH)

    def batch_fn(rnd: int):
        per_node = [
            [
                pipeline.host_batch(
                    cfg, shape, step=rnd * LOCAL_STEPS + h, seed=SEED + 1 + v
                )
                for h in range(LOCAL_STEPS)
            ]
            for v in range(n_nodes)
        ]
        return {
            key: np.stack([np.stack([b[key] for b in bs]) for bs in per_node])
            for key in ("tokens", "labels")
        }

    return batch_fn


@jax.jit
def _fingerprint(params):
    return jnp.stack(
        [jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(params)]
    )


def phase_fl(cfg):
    mesh = mesh_lib.make_mesh((1,), ("data",))
    opt_cfg = adamw.OptConfig(dtype=cfg.opt_dtype)
    fl_cfg = fl_train.FLConfig(mode="tdm", local_steps=LOCAL_STEPS)
    log(f"FL node: {cfg.name} at published widths, batch {BATCH}x{SEQ}, "
        f"{LOCAL_STEPS} local steps/round, AdamW moments {cfg.opt_dtype}")
    state = fl_train._stack_init(jax.random.PRNGKey(SEED), cfg, opt_cfg, 1, mesh)
    log(f"node state on chip: "
        f"{_chip_gib(mesh.devices.flat, 'bytes_in_use')[0]:.2f} GiB")
    before = np.asarray(_fingerprint(state["params"]))
    cache = fl_train.RoundFnCache(cfg, opt_cfg, mesh, 1, fl_cfg)
    stamps = [time.perf_counter()]
    res = fl_train.run(fl_train.TDMRun(
        cache, state, [Relation.from_edges([], nodes=[0])] * FL_ROUNDS,
        make_batch_fn(cfg, 1),
        on_round=lambda _: stamps.append(time.perf_counter()),
    ))
    losses = [lg.loss for lg in res.logs]
    log(f"losses {losses}; round wall s "
        f"{[round(b - a, 3) for a, b in zip(stamps, stamps[1:])]} (the first "
        f"compiles; every round also logs consensus on the host)")
    check(len(losses) == FL_ROUNDS and all(math.isfinite(x) for x in losses),
          "FL losses finite")
    after = np.asarray(_fingerprint(res.state["params"]))
    check(bool(np.any(before != after)), "FL params moved")


# ---------------------------------------------------------------------------
# phase 3: one decode replica behind the serving engine
# ---------------------------------------------------------------------------

def plain_decode(bundle, params, prompt, max_new: int):
    """Greedy tokens from a plain prefill/decode loop, with the prompt
    left-padded to the decoder's prompt bucket as the replica does."""
    plen = ModelDecoder._bucket(len(prompt))
    toks = np.zeros((1, plen), np.int32)
    toks[0, plen - len(prompt):] = prompt
    prefill = jax.jit(bundle.prefill_fn, static_argnums=2)
    decode = jax.jit(bundle.decode_fn)
    logits, cache = prefill(params, {"tokens": jnp.asarray(toks)}, MAX_LEN)
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    out = [int(tok[0])]
    while len(out) < max_new:
        logits, cache = decode(params, cache, {"token": tok[:, None]})
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        out.append(int(tok[0]))
    return out


def phase_serving(cfg):
    scn = smoke_scenario()
    replica = 0
    t0 = time.perf_counter()
    decoder = ModelDecoder(cfg, 1, 1, max_len=MAX_LEN, seed=SEED)
    log(f"decode replica ready in {time.perf_counter() - t0:.1f} s")
    fleet = ReplicaFleet([replica], 1, decoder)
    eng = ServingEngine.from_scenario(scn, fleet)
    workload = synthesize_workload(
        N_REQUESTS, scn.ground_ids, rate_per_slot=1.0, max_new=MAX_NEW, seed=SEED
    )
    t0 = time.perf_counter()
    report = eng.run(workload)
    wall = time.perf_counter() - t0
    summ = report.summary()
    log(f"serving: {summ['delivered']}/{summ['n_requests']} delivered in "
        f"{summ['n_slots']} slots, {summ['tokens']} tokens, {wall:.1f} s wall "
        f"including compiles")
    check(summ["delivered"] == N_REQUESTS and summ["undelivered"] == 0,
          "every request delivered")
    verdict = audit_serving_run(
        report.records, report.requests, eng.base_rels,
        gateways=eng.gateways, replicas=[replica],
    )
    check(verdict.ok, f"route-provenance audit ({verdict.n_hops} hops)")
    first = min(report.requests, key=lambda r: r.rid)
    want = plain_decode(decoder.bundle, decoder.params, first.prompt, first.max_new)
    log(f"request {first.rid}: served {list(first.out)} plain {want}")
    check(list(first.out) == want, "first request's tokens equal plain decode")


# ---------------------------------------------------------------------------
# --chips 4: four FL nodes, one per chip, one TDM round per compression
# ---------------------------------------------------------------------------

WINDOW = 1 << 22  # leading elements of each node's fused buffer, to host


def _np_mix(W: np.ndarray, x: np.ndarray, mode: str):
    """Host Metropolis mix of node rows ``x`` (whole blocks): returns
    (expected rows, per-element slack beyond f32 rounding)."""
    if mode == "none":
        return W @ x, 0.0
    blocks = x.reshape(x.shape[0], -1, BLOCK)
    scale = np.maximum(np.abs(blocks).max(axis=2), np.float32(1e-12))
    scale = scale / np.float32(127)
    q = np.clip(np.rint(blocks / scale[..., None]), -127, 127)
    deq = (q * scale[..., None]).reshape(x.shape)
    off = W - np.diag(np.diag(W))
    want = np.diag(W)[:, None] * x + off @ deq
    # a rounding tie may land one int8 step apart on either side
    return want, 1.01 * np.repeat(off @ scale, BLOCK, axis=1)


@jax.vmap
def _flatten_nodes(params):
    """Stacked params -> stacked ``(node, len)`` fused f32 buffers."""
    (buf,) = fused.flatten_pytree(fused.cached_spec(params, BLOCK), params).values()
    return buf


def _exchange(mesh, n, rel, mode, impl, n_leaves):
    """The fused exchange of one TDM slot on stacked ``(node, len)`` fused
    buffers; with ``against`` it returns instead the count of elements
    outside rtol=atol=1e-6 of ``against`` (so two full outputs never share
    a chip's memory with the exchange's own temporaries)."""
    cfg = fl.TDMFLAConfig(compression=mode)
    P = jax.sharding.PartitionSpec

    def body(buf, *against):
        out, _ = fused.fused_buffer_mix(
            buf[0], rel, "data", n, cfg, n_leaves=n_leaves, quant_impl=impl
        )
        if not against:
            return out[None]
        ref = against[0][0]
        return jnp.sum(jnp.abs(out - ref) > ATOL + RTOL * jnp.abs(ref))[None]

    def fn(buf, *against):
        return jax.shard_map(
            body, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
            check_vma=False,
        )(buf, *against)

    return jax.jit(fn)


def phase_four_chips(cfg):
    """Four FL nodes, one per chip: one int8 TDM round through
    ``build_fl_round``, then the fused exchange in each mode (none, int8,
    top-k) on the trained params against the jnp oracle, and for none/int8
    a leading window against a host numpy Metropolis mix. A top-k round
    (fused CHOCO state next to the AdamW state) does not fit one v5e at
    these widths, so top-k runs the exchange alone."""
    n = 4
    cfg = cfg.replace(opt_dtype="bfloat16")
    mesh = mesh_lib.make_mesh((n,), ("data",))
    opt_cfg = adamw.OptConfig(dtype=cfg.opt_dtype)
    rel = Relation.from_edges([(0, 1), (2, 3)], nodes=range(n))
    W = gossip.metropolis_weights(rel, n).astype(np.float32)
    log(f"{n} nodes, one per chip: {cfg.name} at published widths, batch "
        f"{BATCH}x{SEQ}, {LOCAL_STEPS} local steps, AdamW moments "
        f"{cfg.opt_dtype}; relation {sorted(rel.pairs)} (one TDM matching)")
    state = fl_train._stack_init(jax.random.PRNGKey(SEED), cfg, opt_cfg, n, mesh)
    per_chip = _chip_gib(mesh.devices.flat, "bytes_in_use")
    log(f"node state per chip (GiB): {[round(x, 3) for x in per_chip]}")
    check(max(per_chip) < 1.5 * min(per_chip), "one node per chip")
    before = np.asarray(_fingerprint(state["params"]))
    batch = make_batch_fn(cfg, n)(0)
    fl_cfg = fl_train.FLConfig(
        mode="tdm", local_steps=LOCAL_STEPS, compression="int8"
    )
    t0 = time.perf_counter()
    step = _compile(
        fl_train.build_fl_round(cfg, opt_cfg, mesh, n, fl_cfg, rel),
        state, batch, kernel="int8 FL round",
    )
    t1 = time.perf_counter()
    state, losses = step(state, batch)
    losses = np.asarray(losses)
    log(f"int8 FL round compiled in {t1 - t0:.1f} s, ran in "
        f"{time.perf_counter() - t1:.1f} s; losses {losses.tolist()}")
    check(bool(np.all(np.isfinite(losses))), "int8 round losses finite")
    peaks = _chip_gib(mesh.devices.flat, "peak_bytes_in_use")
    log(f"peak bytes in use per chip after the round (GiB): "
        f"{[round(p, 3) for p in peaks]}")
    params = state["params"]
    del state
    check(bool(np.any(np.asarray(_fingerprint(params)) != before)),
          "params moved")
    n_leaves = len(jax.tree.leaves(params))  # one f32 bucket holds them all
    buf = jax.jit(_flatten_nodes)(params)
    del params
    x = np.asarray(buf[:, :WINDOW])
    for mode in ("int8", "topk", "none"):
        t0 = time.perf_counter()
        mix, cmp = (
            _exchange(mesh, n, rel, mode, impl, n_leaves)
            for impl in ("auto", "ref")
        )
        mix = _compile(mix, buf,
                       kernel="" if mode == "none" else f"{mode} exchange")
        out = mix(buf)
        bad = int(np.sum(np.asarray(cmp(buf, out))))
        dev = np.asarray(out[:, :WINDOW])
        del out
        log(f"{mode}: exchange and ref check {time.perf_counter() - t0:.1f} s "
            f"(compiles included)")
        check(bad == 0, f"{mode}: Pallas exchange vs ref within rtol=atol={RTOL:g}")
        if mode != "topk":
            want, slack = _np_mix(W, x, mode)
            err = np.abs(dev - want)
            check(bool(np.all(err <= 1e-6 + 1e-5 * np.abs(want) + slack)),
                  f"{mode}: exchange vs host numpy Metropolis mix "
                  f"({WINDOW} elements per node)")
    peaks = _chip_gib(mesh.devices.flat, "peak_bytes_in_use")
    log(f"peak bytes in use per chip (GiB): {[round(p, 3) for p in peaks]}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    devs = require_tpu(args.chips)
    use_compile_cache()
    cfg = model_config()
    log(f"device {devs[0].device_kind} x{len(devs)}; {cfg.name}: "
        f"{cfg.param_count():,} parameters (smoke run, not a benchmark)")
    phases = (
        [("four-chip FL", phase_four_chips)]
        if args.chips == 4
        else [("kernels", phase_kernels), ("FL node", phase_fl),
              ("serving", phase_serving)]
    )
    for name, phase in phases:
        t0 = time.perf_counter()
        phase(cfg)
        log(f"phase {name} done in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }}))


if __name__ == "__main__":
    main()
