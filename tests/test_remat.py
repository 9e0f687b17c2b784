"""The rematerialisation plan of the training forward: the backward
recomputes each layer from its input and the values saved by name
(``mamba2.SAVED``), so a Mamba-2 layer's in-projection matmuls and the SSD
scan's quadratic chunk work run once in the forward and are not run again
to rebuild the layer's activations."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.configs import archs
from repro.core.relation import Relation
from repro.launch import fl_train, flops
from repro.launch import mesh as mesh_lib
from repro.models import registry, transformer
from repro.optim import adamw

B, S = 2, 16  # two SSD chunks at the smoke configs' chunk of 8


def _batch(cfg, seed=0):
    rng = np.random.default_rng(seed)
    return {
        k: jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
        for k in ("tokens", "labels")
    }


def _mamba_sizes(cfg):
    """(elements a token of the saved values, matmul FLOPs a token of the
    in-projections and of the SSD output's chunk work, Mamba layers)."""
    mb = cfg.mamba
    D, di = cfg.d_model, mb.d_inner(cfg.d_model)
    gn, hm, P, N = mb.n_groups * mb.d_state, mb.n_heads(cfg.d_model), mb.head_dim, mb.d_state
    Q = min(mb.chunk, S)
    in_proj = 2 * D * (2 * di + 2 * gn + hm)
    # C.B, the intra-chunk product and the inter-chunk read of the state
    ssd_out = 2 * hm * Q * N + 2 * hm * Q * P + 2 * hm * N * P
    n_mamba = sum(
        d.mixer != "attn" for d in transformer.scan_unit(cfg)
    ) * transformer.n_units(cfg)
    return 3 * di + 2 * gn + hm, in_proj + ssd_out, n_mamba


def _grad(cfg):
    b = registry.bundle(cfg)
    return lambda p, batch: jax.grad(lambda q: b.loss_fn(q, batch)[0])(p)


# the Mamba-2 smoke config, and a hybrid cut to one attention + dense MLP
# and one Mamba + MoE layer per unit
TINY = {
    "mamba2-780m": {},
    "jamba-1.5-large-398b": dict(attn_every=2, n_layers=4),
}


@pytest.mark.parametrize("name", sorted(TINY))
def test_full_remat_matches_no_remat(name):
    cfg = archs.smoke_cfg(archs.get(name)).replace(**TINY[name])
    params, _ = registry.bundle(cfg).init(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    out = {}
    for remat in ("none", "full"):
        b = registry.bundle(cfg.replace(remat=remat))
        out[remat] = jax.jit(
            jax.value_and_grad(lambda p: b.loss_fn(p, batch)[0])
        )(params)
    (l0, g0), (l1, g1) = out["none"], out["full"]
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-6)
    for a, b_ in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(
            np.asarray(b_, np.float32), np.asarray(a, np.float32), rtol=1e-5, atol=1e-7
        )


def test_saved_values_are_not_recomputed(monkeypatch):
    """Against a plain layer checkpoint, the gradient executes one forward's
    in-projection matmuls and SSD-output matmuls fewer, and nothing else."""
    from bench import flops as bench_flops

    cfg = archs.smoke_cfg(archs.get("mamba2-780m"))
    params, _ = registry.bundle(cfg).init(jax.random.PRNGKey(0))
    batch = _batch(cfg)

    def matmul_flops():
        return bench_flops.matmul_flops(jax.make_jaxpr(_grad(cfg))(params, batch).jaxpr)

    named = matmul_flops()
    monkeypatch.setattr(transformer, "remat_layer", lambda body, cfg: jax.checkpoint(body))
    plain = matmul_flops()
    _, flops_per_token, n_mamba = _mamba_sizes(cfg)
    assert plain - named == B * S * flops_per_token * n_mamba


@pytest.mark.parametrize("name", ["mamba2-780m", "jamba-1.5-large-398b", "gemma2-9b"])
@pytest.mark.parametrize("remat", ["none", "full"])
def test_remat_saved_bytes_counts_the_named_projections(name, remat):
    cfg = archs.smoke_cfg(archs.get(name)).replace(remat=remat)
    b = registry.bundle(cfg)
    params = jax.eval_shape(lambda k: b.init(k)[0], jax.random.PRNGKey(0))
    saved = flops.remat_saved_bytes(lambda p, x: b.loss_fn(p, x)[0], params, _batch(cfg))
    if cfg.mamba is None or remat == "none":
        assert saved == 0
    else:
        per_token, _, n_mamba = _mamba_sizes(cfg)
        itemsize = jnp.dtype(cfg.compute_dtype).itemsize
        assert saved == B * S * per_token * itemsize * n_mamba


@pytest.mark.parametrize("name", ["mamba2-780m", "gemma2-9b"])
def test_round_cache_records_remat_gauge_once(name, monkeypatch):
    cfg = archs.smoke_cfg(archs.get(name))
    opt_cfg = adamw.OptConfig()
    mesh = mesh_lib.make_mesh((1,), ("data",))
    cache = fl_train.RoundFnCache(cfg, opt_cfg, mesh, 1, fl_train.FLConfig())
    rel = Relation.from_edges([], nodes=range(1))
    b = registry.bundle(cfg)
    params = jax.eval_shape(lambda k: b.init(k)[0], jax.random.PRNGKey(0))
    state = {"params": jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((1,) + x.shape, x.dtype), params)}
    batch = {k: jax.ShapeDtypeStruct((1, 1, B, S), jnp.int32) for k in ("tokens", "labels")}
    calls = []
    count = flops.remat_saved_bytes
    monkeypatch.setattr(flops, "remat_saved_bytes", lambda *a: calls.append(1) or count(*a))

    with telemetry.record_scope(tracing=False) as rec:
        cache(rel, example_args=(state, batch))
    assert "fl.remat_saved_bytes" not in rec.gauges and not calls

    cache = fl_train.RoundFnCache(cfg, opt_cfg, mesh, 1, fl_train.FLConfig())
    with telemetry.record_scope(tracing=True) as rec:
        for _ in range(2):
            cache(rel, example_args=(state, batch))
        gauges = telemetry.metrics_snapshot(rec)["gauges"]
    assert len(calls) == 1
    expected = 0
    if cfg.mamba is not None:
        per_token, _, n_mamba = _mamba_sizes(cfg)
        expected = B * S * per_token * jnp.dtype(cfg.compute_dtype).itemsize * n_mamba
    assert gauges["fl.remat_saved_bytes"] == expected
