"""Per-architecture smoke tests: reduced same-family configs run one
forward/train step (and a prefill+decode round trip) on CPU, asserting
output shapes and finiteness. The FULL configs are exercised only via the
dry-run (ShapeDtypeStruct, no allocation)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import archs
from repro.models import registry

ARCH_NAMES = list(archs.ARCHS.keys())


def make_batch(cfg, B=2, S=32, seed=0):
    rng = np.random.default_rng(seed)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32),
    }
    if cfg.mrope_sections is not None:
        pos = np.broadcast_to(np.arange(S)[None, :, None], (B, S, 3)).copy()
        pos[:, :, 1] += rng.integers(0, 3, (B, S))  # fake 2D offsets
        batch["positions"] = jnp.asarray(pos, jnp.int32)
    if cfg.enc_dec:
        batch["enc_embeds"] = jnp.asarray(
            rng.normal(size=(B, cfg.enc_frames, cfg.d_model)), jnp.bfloat16
        )
    return batch


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_train_step_smoke(name):
    cfg = archs.smoke_cfg(archs.get(name))
    b = registry.bundle(cfg)
    params, specs = b.init(jax.random.PRNGKey(0))
    # specs mirror params
    jax.tree.map(
        lambda p, s: None, params, specs,
        is_leaf=lambda x: isinstance(x, tuple) and not isinstance(x, jnp.ndarray),
    )
    batch = make_batch(cfg)

    loss, metrics = jax.jit(b.loss_fn)(params, batch)
    assert jnp.isfinite(loss), (name, loss)
    assert float(loss) > 0
    # one gradient step moves the loss
    grads = jax.jit(jax.grad(lambda p: b.loss_fn(p, batch)[0]))(params)
    gnorm = jnp.sqrt(
        sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(grads))
    )
    assert jnp.isfinite(gnorm) and float(gnorm) > 0, name
    lr = 1e-2
    new_params = jax.tree.map(
        lambda p, g: p - lr * g.astype(p.dtype) if jnp.issubdtype(p.dtype, jnp.floating) else p,
        params, grads,
    )
    loss2, _ = jax.jit(b.loss_fn)(new_params, batch)
    assert jnp.isfinite(loss2), name
    assert float(loss2) < float(loss) * 1.5  # sanity: no explosion


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_prefill_decode_smoke(name):
    cfg = archs.smoke_cfg(archs.get(name))
    b = registry.bundle(cfg)
    params, _ = b.init(jax.random.PRNGKey(1))
    B, S, max_len = 2, 16, 32
    batch = make_batch(cfg, B=B, S=S, seed=1)
    pre_batch = {k: v for k, v in batch.items() if k != "labels"}
    if "positions" in pre_batch:
        pre_batch["positions"] = pre_batch["positions"][:, :S]

    logits, cache = jax.jit(
        lambda p, bt: b.prefill_fn(p, bt, max_len)
    )(params, pre_batch)
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all()), name
    assert int(cache["pos"]) == S

    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    step_batch = {"token": tok}
    if cfg.mrope_sections is not None:
        step_batch["positions"] = jnp.full((B, 1, 3), S, jnp.int32)
    logits2, cache2 = jax.jit(b.decode_fn)(params, cache, step_batch)
    assert logits2.shape == (B, 1, cfg.vocab_size)
    assert bool(jnp.isfinite(logits2.astype(jnp.float32)).all()), name
    assert int(cache2["pos"]) == S + 1


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_prefill_matches_decode_replay(name):
    """Decoding token-by-token from an empty cache reproduces the prefill
    logits (the core cache-consistency invariant, incl. ring caches).

    fp32 compute: prefill (chunked SSD / blocked attention) and decode
    (recurrence) sum in different orders, so bf16 noise would mask real
    cache bugs. fp32 separates the two (observed: bf16 ~0.1, fp32 ~1e-5)."""
    cfg = archs.smoke_cfg(archs.get(name)).replace(compute_dtype="float32")
    b = registry.bundle(cfg)
    params, _ = b.init(jax.random.PRNGKey(2))
    B, S = 1, 8
    batch = make_batch(cfg, B=B, S=S, seed=2)
    pre_batch = {k: v for k, v in batch.items() if k != "labels"}

    logits_pre, cache_pre = jax.jit(
        lambda p, bt: b.prefill_fn(p, bt, S + 4)
    )(params, pre_batch)

    cache = b.init_cache(B, S + 4)
    if cfg.enc_dec:
        # replay needs the cross-attn KV: take it from a length-0 prefill
        # trick — run prefill on the first token to fill cross KV, then
        # continue decoding from scratch positions. Simpler: copy cross KV.
        for key in cache["units"]:
            if key.startswith("cross"):
                cache["units"][key] = cache_pre["units"][key]
    logits = None
    decode = jax.jit(b.decode_fn)
    for t in range(S):
        sb = {"token": batch["tokens"][:, t : t + 1]}
        if cfg.mrope_sections is not None:
            sb["positions"] = batch["positions"][:, t : t + 1]
        logits, cache = decode(params, cache, sb)
    np.testing.assert_allclose(
        np.asarray(logits, np.float32),
        np.asarray(logits_pre, np.float32),
        rtol=2e-3, atol=2e-3,
    )


def test_param_counts_match_published_sizes():
    """Exact param counts land near the published model sizes."""
    expect = {
        "mamba2-780m": (0.6e9, 1.0e9),
        "gemma2-9b": (8.0e9, 10.5e9),
        "gemma2-27b": (24e9, 29e9),
        "granite-20b": (18e9, 22e9),
        "qwen2-72b": (68e9, 76e9),
        "jamba-1.5-large-398b": (370e9, 420e9),
        "qwen3-moe-30b-a3b": (28e9, 33e9),
        "nemotron-3-nano-30b-a3b": (31e9, 32e9),
        "kimi-k2-1t-a32b": (0.95e12, 1.1e12),
        "whisper-base": (0.05e9, 0.11e9),
        "qwen2-vl-72b": (68e9, 76e9),
    }
    for name, (lo, hi) in expect.items():
        n = archs.get(name).param_count()
        assert lo <= n <= hi, f"{name}: {n/1e9:.2f}B not in [{lo/1e9}, {hi/1e9}]B"


def test_active_param_counts():
    assert 2e9 <= archs.get("qwen3-moe-30b-a3b").active_param_count() <= 4.5e9
    assert 25e9 <= archs.get("kimi-k2-1t-a32b").active_param_count() <= 40e9


def test_cell_enumeration():
    cells = list(archs.all_cells())
    # 11 archs x 4 shapes - 8 long_500k skips (full-attention archs)
    assert len(cells) == 36
    longs = [c for c in cells if c[1] == "long_500k"]
    assert sorted(x[0] for x in longs) == [
        "jamba-1.5-large-398b", "mamba2-780m", "nemotron-3-nano-30b-a3b"
    ]
