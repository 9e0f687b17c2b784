"""The plain reference of an FL round, and the comparison that decides
``correct``.

A round is, on every node, one AdamW step per local step on the node's own
batch, then (where the slot's relation gives the node peers) a Metropolis
gossip step whose payloads are the node's parameters, flattened in pytree
order into one buffer padded to whole blocks and quantized blockwise to
int8 (scale = max(absmax, 1e-12) / 127, round half to even, clip to
+-127). Node ``i`` keeps ``W_ii x_i + sum_j W_ij deq(q_j)``, with
``W_ij = 1 / (1 + max(d_i, d_j))`` over its peers ``j``.

Everything here is written from that description and from the model's own
reference module (``bench/configs/<config>.py``); nothing is imported from
the program. Each node's reference runs on the chip that held the node, in
float32 at the highest matmul precision, one node and one step at a time.
"""

from __future__ import annotations

import math
import time
import warnings
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = "highest"


# ---------------------------------------------------------------------------
# AdamW, as the configuration states it
# ---------------------------------------------------------------------------

def learning_rate(step: int, opt: dict) -> float:
    """Linear warm-up to ``peak_lr`` over ``warmup_steps`` (step 0 takes
    1/warmup of it), then cosine decay to ``end_lr_frac`` of it."""
    if step < opt["warmup_steps"]:
        return opt["peak_lr"] * (step + 1) / max(opt["warmup_steps"], 1)
    t = min(max((step - opt["warmup_steps"])
                / max(opt["decay_steps"] - opt["warmup_steps"], 1), 0.0), 1.0)
    f = opt["end_lr_frac"]
    return opt["peak_lr"] * (f + (1 - f) * 0.5 * (1 + math.cos(math.pi * t)))


def adamw(params, grads, mu, nu, lr, t, opt: dict, moment_dtype):
    """One AdamW step with global-norm clipping at learning rate ``lr`` and
    step number ``t`` (from 1). Returns (params, mu, nu, the clipped
    gradient); moments are kept in ``moment_dtype``."""
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    clip = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-9))
    b1, b2 = opt["b1"], opt["b2"]

    def leaf(p, g, m, v):
        g = g * clip
        m = b1 * m.astype(jnp.float32) + (1 - b1) * g
        v = b2 * v.astype(jnp.float32) + (1 - b2) * g * g
        upd = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + opt["eps"])
        p = p - lr * (upd + opt["weight_decay"] * p)
        return p, m.astype(moment_dtype), v.astype(moment_dtype), g

    out = jax.tree.map(leaf, params, grads, mu, nu)
    is_out = lambda o: isinstance(o, tuple)
    return tuple(jax.tree.map(lambda o: o[k], out, is_leaf=is_out) for k in range(4))


# ---------------------------------------------------------------------------
# the exchange
# ---------------------------------------------------------------------------

def metropolis(n: int, edges) -> np.ndarray:
    deg = np.zeros(n, int)
    for a, b in edges:
        deg[a] += 1
        deg[b] += 1
    W = np.zeros((n, n))
    for a, b in edges:
        W[a, b] = W[b, a] = 1.0 / (1 + max(deg[a], deg[b]))
    W[np.arange(n), np.arange(n)] = 1.0 - W.sum(axis=1)
    return W


def flatten(params, block: int):
    leaves = [x.reshape(-1) for x in jax.tree.leaves(params)]
    flat = jnp.concatenate(leaves)
    return jnp.pad(flat, (0, (-flat.shape[0]) % block))


def quantize(flat, block: int):
    xb = flat.reshape(-1, block)
    scale = jnp.maximum(jnp.max(jnp.abs(xb), axis=1), 1e-12) / 127.0
    q = jnp.clip(jnp.round(xb / scale[:, None]), -127, 127).astype(jnp.int8)
    return q.reshape(-1), scale


def mix(params, self_w, peers, block: int):
    """``self_w * x + sum_j w_j * deq(q_j, s_j)`` over ``peers`` =
    [(w_j, q_j, s_j), ...], leaf by leaf: each leaf reads its own span of
    the peers' flat payloads, with the scale of the block each element
    lies in (blocks run across leaf boundaries)."""
    leaves, treedef = jax.tree.flatten(params)
    out, off = [], 0
    for x in leaves:
        n = x.size
        b0, b1 = off // block, -(-(off + n) // block)
        acc = jnp.zeros((n,), jnp.float32)
        for w, q, s in peers:
            scale = jnp.repeat(s[b0:b1], block)[off - b0 * block:off - b0 * block + n]
            acc = acc + w * (q[off:off + n].astype(jnp.float32) * scale)
        out.append((self_w * x.reshape(-1) + acc).reshape(x.shape))
        off += n
    return jax.tree.unflatten(treedef, out)


# ---------------------------------------------------------------------------
# the reference run and its readings
# ---------------------------------------------------------------------------

def _gib_in_use(devices) -> str:
    stats = [d.memory_stats() or {} for d in devices]
    return f"{max(st.get('bytes_in_use', 0) for st in stats) / 2**30:.2f} GiB in use"


def leaf_norms(tree) -> jax.Array:
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def run(model, conf: dict, mix_cfg: dict, batches: List[dict], key, devices,
        steps: int, cast: Callable = lambda a: a, fault: str = "",
        log: Callable = lambda msg: None) -> Dict[str, np.ndarray]:
    """``steps`` reference rounds of every node from the weights ``key``
    makes. Returns the readings the comparison uses: ``loss`` (steps,
    nodes), ``grad_norm`` (nodes, leaves) of the first step's clipped
    gradient, and ``update_norm`` (nodes, leaves) of the parameters' change
    after the last round. ``fault`` plants one of the faults the check must
    catch (``half_batch``, ``no_exchange``, ``frozen``), for calibration."""
    n, H = mix_cfg["nodes"], mix_cfg["local_steps"]
    opt = conf["assumed"]["optimizer"]
    mdt = jnp.dtype(conf["assumed"]["opt_dtype"])
    block = mix_cfg.get("block", 1024)
    W = metropolis(n, mix_cfg["edges"])
    peers = [[j for j in range(n) if j != i and W[i, j] > 0] for i in range(n)]
    exchange = any(peers) and fault not in ("no_exchange", "frozen")
    devs = devices[:n]

    # the gradient's buffers are donated to AdamW, which cannot use them all
    warnings.filterwarnings("ignore", message="Some donated buffers were not usable")
    with jax.default_matmul_precision(HIGHEST):
        init = jax.jit(lambda k: model.init(k, conf))

        block_grad = jax.jit(jax.value_and_grad(lambda p, b: model.loss(p, b, conf, cast)))
        add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)
        scale = jax.jit(lambda a, c: jax.tree.map(lambda x: x * c, a), donate_argnums=0)
        rows = getattr(model, "ROW_BLOCK", None)

        def grad(p, b):
            """Loss and gradient of the batch's token mean, taken over
            blocks of ``rows`` rows where the model asks for it."""
            if fault == "half_batch":
                b = jax.tree.map(lambda x: x[: x.shape[0] // 2], b)
            n_rows = b["tokens"].shape[0]
            if not rows or n_rows <= rows:
                return block_grad(p, b)
            if n_rows % rows:
                raise ValueError(f"{n_rows} rows do not split into blocks of {rows}")
            total = g = None
            for lo in range(0, n_rows, rows):
                l_k, g_k = block_grad(p, jax.tree.map(lambda x: x[lo:lo + rows], b))
                total = l_k if total is None else total + l_k
                g = g_k if g is None else add(g, g_k)
            k = n_rows // rows
            return total / k, scale(g, np.float32(1.0 / k))
        step_fn = jax.jit(
            lambda p, g, m, v, lr, t: adamw(p, g, m, v, lr, t, opt, mdt),
            donate_argnums=(0, 1, 2, 3),
        )
        zeros = jax.jit(lambda p: jax.tree.map(lambda x: jnp.zeros(x.shape, mdt), p))
        norms = jax.jit(leaf_norms)
        delta = jax.jit(lambda a, b: leaf_norms(jax.tree.map(jnp.subtract, a, b)))
        payload_fn = jax.jit(lambda p: quantize(flatten(p, block), block))
        mix_fn = jax.jit(lambda p, w, pr: mix(p, w, pr, block), donate_argnums=0)

        t0 = time.perf_counter()
        keys = [jax.device_put(key, d) for d in devs]
        params = [init(k) for k in keys]
        mu = [zeros(p) for p in params]
        nu = [zeros(p) for p in params]
        jax.block_until_ready((mu, nu))
        log(f"reference: weights {time.perf_counter() - t0:.1f} s, {_gib_in_use(devs)}")
        losses = [[0.0] * n for _ in range(steps)]
        grad_norm = [None] * n
        for s in range(steps):
            for i, d in enumerate(devs):
                for h in range(H):
                    t = s * H + h
                    b = jax.device_put({k: v[i, h] for k, v in batches[s].items()}, d)
                    loss, g = grad(params[i], b)
                    losses[s][i] = losses[s][i] + loss / H
                    new, mu[i], nu[i], gc = step_fn(
                        params[i] if fault != "frozen" else jax.tree.map(jnp.copy, params[i]),
                        g, mu[i], nu[i], np.float32(learning_rate(t, opt)), np.float32(t + 1))
                    if fault != "frozen":
                        params[i] = new
                    if t == 0:
                        grad_norm[i] = norms(gc)
                    del g, gc, new
            jax.block_until_ready(params)
            log(f"reference: step {s + 1} {time.perf_counter() - t0:.1f} s, {_gib_in_use(devs)}")
            if not exchange:
                continue
            payloads = [payload_fn(params[i]) for i in range(n)]
            for i, d in enumerate(devs):
                pr = tuple((np.float32(W[i, j]), *jax.device_put(payloads[j], d))
                           for j in peers[i])
                params[i] = mix_fn(params[i], np.float32(W[i, i]), pr)
            del payloads
            jax.block_until_ready(params)
            log(f"reference: exchange {s + 1} {time.perf_counter() - t0:.1f} s, "
                f"{_gib_in_use(devs)}")
        update_norm = []
        for i in range(n):
            p0 = init(keys[i])
            update_norm.append(np.asarray(delta(params[i], p0)))
            del p0
    return {
        "loss": np.array([[float(x) for x in row] for row in losses]),
        "grad_norm": np.stack([np.asarray(g) for g in grad_norm]),
        "update_norm": np.stack(update_norm),
    }


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------

def _norm_gap(prog: np.ndarray, ref: np.ndarray, keep=None) -> float:
    """Worst (node, leaf) gap between the program's norm and the
    reference's, over the larger of that leaf's reference norm and the
    node's median leaf norm."""
    worst = 0.0
    for i in range(ref.shape[0]):
        floor = np.median(ref[i])
        for j in range(ref.shape[1]):
            if keep is not None and not keep[i, j]:
                continue
            worst = max(worst, abs(prog[i, j] - ref[i, j]) / max(ref[i, j], floor))
    return float(worst)


def kept_leaves(ref: Dict[str, np.ndarray]) -> np.ndarray:
    """Leaves whose first reference gradient is at least a thousandth of
    the node's median leaf; the others move by round-off alone and are
    left out of the update's comparison."""
    g = ref["grad_norm"]
    return g >= 1e-3 * np.median(g, axis=1, keepdims=True)


def compare(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray]) -> Dict[str, float]:
    """The numbers ``correct`` is decided on (see PERF.md for their limits)."""
    g, keep = ref["grad_norm"], kept_leaves(ref)
    loss_gap = float(np.max(np.abs(prog["loss"] - ref["loss"]) / np.abs(ref["loss"])))
    return {
        "loss_gap": loss_gap,
        "grad_norm_gap": _norm_gap(prog["grad_norm"], g),
        "update_norm_gap": _norm_gap(prog["update_norm"], ref["update_norm"], keep),
    }
