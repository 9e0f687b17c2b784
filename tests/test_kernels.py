"""Per-kernel validation: sweep shapes/dtypes, assert_allclose against the
ref.py pure-jnp oracles. Kernels run in interpret=True mode (the container
is CPU; TPU is the compile target).

The ``tdm_compress`` family additionally gets a DIFFERENTIAL suite: the
Pallas kernels must match the jnp oracles BIT FOR BIT across random shapes
× k × block (via the proptest shim) and adversarial edges (ragged tails,
k=0, k=block, all-equal magnitudes, NaN/inf payloads). Both sides run
under ``jax.jit`` — XLA contracts ``a + w*v`` into an FMA under jit but
not in eager op-by-op execution, so comparing a jitted kernel against an
eager oracle shows spurious 1-ulp diffs that say nothing about the kernel.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from proptest import given, st_choice, st_int

from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.ssd_scan import ops as ssd_ops
from repro.kernels.ssd_scan import ref as ssd_ref
from repro.kernels.tdm_compress import ops as q_ops
from repro.kernels.tdm_compress import ref as q_ref
from repro.models.attention import AttnSpec, flash_attention_train, naive_attention
from repro.models import mamba2 as mamba_lib


def rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# ---------------------------------------------------------------------------
# flash attention kernel vs oracle
# ---------------------------------------------------------------------------

FA_CASES = [
    # (B, Sq, Skv, H, KV, hd, causal, window, softcap, dtype)
    (1, 256, 256, 2, 2, 64, True, None, None, jnp.float32),
    (2, 256, 256, 4, 2, 64, True, None, None, jnp.float32),      # GQA
    (1, 384, 384, 4, 1, 64, True, None, None, jnp.float32),      # MQA
    (1, 256, 256, 2, 2, 64, True, 128, None, jnp.float32),       # window
    (1, 256, 256, 2, 2, 64, True, None, 50.0, jnp.float32),      # softcap
    (1, 256, 256, 2, 2, 64, False, None, None, jnp.float32),     # bidi
    (2, 256, 256, 4, 2, 128, True, 128, 30.0, jnp.float32),      # all
    (1, 256, 256, 2, 2, 64, True, None, None, jnp.bfloat16),
    (1, 128, 512, 2, 2, 96, False, None, None, jnp.float32),     # cross, pad hd
]


@pytest.mark.parametrize("case", FA_CASES)
def test_flash_kernel_matches_ref(case):
    B, Sq, Skv, H, KV, hd, causal, window, softcap, dtype = case
    ks = jax.random.split(jax.random.PRNGKey(hash(case) % 2**31), 3)
    q = rand(ks[0], (B, Sq, H, hd), dtype)
    k = rand(ks[1], (B, Skv, KV, hd), dtype)
    v = rand(ks[2], (B, Skv, KV, hd), dtype)
    got = fa_ops.flash_attention(
        q, k, v, causal=causal, window=window, softcap=softcap,
        block_q=128, block_k=128, interpret=True,
    )
    want = fa_ref.attention_ref(
        q, k, v, causal=causal, window=window, softcap=softcap
    )
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


def test_model_flash_matches_kernel_and_ref():
    """Three-way: model XLA path == Pallas kernel == naive oracle."""
    B, S, H, KV, hd = 2, 256, 4, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = rand(ks[0], (B, S, H, hd), jnp.float32)
    k = rand(ks[1], (B, S, KV, hd), jnp.float32)
    v = rand(ks[2], (B, S, KV, hd), jnp.float32)
    spec = AttnSpec(causal=True, window=128, softcap=50.0, block_q=128, block_k=128)
    xla = flash_attention_train(q, k, v, spec)
    kern = fa_ops.flash_attention(
        q, k, v, causal=True, window=128, softcap=50.0,
        block_q=128, block_k=128, interpret=True,
    )
    ref = fa_ref.attention_ref(q, k, v, causal=True, window=128, softcap=50.0)
    np.testing.assert_allclose(np.asarray(xla), np.asarray(ref), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_model_flash_gradients_match_naive():
    """The manual custom_vjp backward == AD through the naive oracle."""
    B, S, H, KV, hd = 1, 64, 2, 1, 32
    spec = AttnSpec(causal=True, window=48, softcap=20.0, block_q=16, block_k=16)
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = rand(ks[0], (B, S, H, hd), jnp.float32)
    k = rand(ks[1], (B, S, KV, hd), jnp.float32)
    v = rand(ks[2], (B, S, KV, hd), jnp.float32)

    def loss_flash(q, k, v):
        return jnp.sum(jnp.sin(flash_attention_train(q, k, v, spec)))

    def loss_naive(q, k, v):
        return jnp.sum(jnp.sin(naive_attention(q, k, v, spec)))

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


# the TPU path of flash_attention_train: the Pallas splash kernels
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_kernel_path_matches_naive(causal, monkeypatch):
    """Through ``flash_attention_train``'s custom_vjp with the backend seen as
    a TPU (kernels in interpret mode), a tiling GQA shape takes the splash
    kernels, and their output and gradients with respect to q, k and v match
    AD through the naive oracle in f32 at HIGHEST precision."""
    import functools

    from repro.models import attention as attn

    B, S, H, KV, hd = 2, 1024, 8, 2, 128
    spec = AttnSpec(causal=causal)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert attn.kernel_applies((B, S, H, hd), (B, S, KV, hd), spec)
    calls = []
    splash = attn.splash_attention

    def interpreted(*args, **kw):
        calls.append(args[0].shape)
        return splash(*args, interpret=True, **kw)

    monkeypatch.setattr(attn, "splash_attention", interpreted)
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    q = rand(ks[0], (B, S, H, hd), jnp.float32)
    k = rand(ks[1], (B, S, KV, hd), jnp.float32)
    v = rand(ks[2], (B, S, KV, hd), jnp.float32)
    w = rand(ks[3], (B, S, H, hd), jnp.float32)

    def loss(attend, q, k, v):
        return jnp.sum(w * attend(q, k, v, spec))

    got = jax.jit(functools.partial(flash_attention_train, spec=spec))(q, k, v)
    grads = jax.jit(jax.grad(functools.partial(loss, flash_attention_train), (0, 1, 2)))(q, k, v)
    assert len(calls) == 2  # the forward, and the custom_vjp's forward
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    with jax.default_matmul_precision("highest"):
        want = naive_attention(q, k, v, spec)
        want_grads = jax.grad(functools.partial(loss, naive_attention), (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)
    for a, b in zip(grads, want_grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


SOLO8K = ((2, 8192, 32, 128), (2, 8192, 2, 128))  # q and k shapes
KERNEL_DISPATCH = {
    # name: (q shape, k shape, spec, kernel on a TPU)
    "causal_gqa": (*SOLO8K, AttnSpec(causal=True), True),
    "full": (*SOLO8K, AttnSpec(causal=False), True),
    "cross": ((2, 1024, 8, 128), (2, 2048, 8, 128), AttnSpec(causal=False), True),
    "head_dim_64": ((2, 1024, 8, 64), (2, 1024, 2, 64), AttnSpec(), False),
    "window": (*SOLO8K, AttnSpec(window=4096), False),
    "softcap": (*SOLO8K, AttnSpec(softcap=50.0), False),
    "length_not_tiling": ((2, 1536, 8, 128), (2, 1536, 2, 128), AttnSpec(), False),
    "shorter_than_a_block": ((2, 512, 8, 128), (2, 512, 2, 128), AttnSpec(), False),
    "heads_not_grouped": ((2, 1024, 6, 128), (2, 1024, 4, 128), AttnSpec(), False),
    "blocks_off_the_lanes": ((2, 1024, 8, 128), (2, 1024, 2, 128),
                             AttnSpec(block_q=64, block_k=64), False),
}


@pytest.mark.parametrize("case", sorted(KERNEL_DISPATCH))
def test_kernel_dispatch_follows_backend_spec_and_shapes(case, monkeypatch):
    """``kernel_applies`` picks the splash kernels only on a TPU, for a causal
    or full spec at shapes that tile; everything else keeps the blocked
    pure-JAX path."""
    from repro.models.attention import kernel_applies

    q_shape, k_shape, spec, on_tpu = KERNEL_DISPATCH[case]
    assert not kernel_applies(q_shape, k_shape, spec)  # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kernel_applies(q_shape, k_shape, spec) == on_tpu


# ---------------------------------------------------------------------------
# SSD kernel vs sequential oracle
# ---------------------------------------------------------------------------

SSD_CASES = [
    # (B, S, H, P, G, N, chunk, dtype)
    (1, 128, 2, 16, 1, 32, 32, jnp.float32),
    (2, 256, 4, 64, 2, 64, 64, jnp.float32),
    (1, 256, 4, 64, 4, 128, 128, jnp.float32),
    (1, 128, 2, 32, 1, 64, 32, jnp.bfloat16),
]


@pytest.mark.parametrize("case", SSD_CASES)
def test_ssd_kernel_matches_sequential_ref(case):
    B, S, H, P, G, N, chunk, dtype = case
    ks = jax.random.split(jax.random.PRNGKey(hash(case) % 2**31), 5)
    xh = rand(ks[0], (B, S, H, P), dtype)
    dt = jax.nn.softplus(rand(ks[1], (B, S, H), jnp.float32))
    A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=-1.0, maxval=1.0))
    Bv = rand(ks[3], (B, S, G, N), dtype)
    Cv = rand(ks[4], (B, S, G, N), dtype)

    y, state = ssd_ops.ssd_scan(xh, dt, A, Bv, Cv, chunk=chunk, interpret=True)

    # oracle in kernel layout
    r = H // G
    xf = xh.transpose(0, 2, 1, 3).reshape(B * H, S, P)
    dtf = dt.transpose(0, 2, 1).reshape(B * H, S)
    Af = jnp.broadcast_to(A[None], (B, H)).reshape(B * H)
    Bh = jnp.broadcast_to(Bv[:, :, :, None, :], (B, S, G, r, N)).transpose(
        0, 2, 3, 1, 4
    ).reshape(B * H, S, N)
    Ch = jnp.broadcast_to(Cv[:, :, :, None, :], (B, S, G, r, N)).transpose(
        0, 2, 3, 1, 4
    ).reshape(B * H, S, N)
    y_ref, state_ref = ssd_ref.ssd_ref(xf, dtf, Af, Bh, Ch)
    y_ref = y_ref.reshape(B, H, S, P).transpose(0, 2, 1, 3)
    state_ref = state_ref.reshape(B, H, P, N)

    # chunked matmuls vs sequential recurrence sum in different orders;
    # fp32 noise grows with N (reduction width) — scale-aware tolerances.
    rtol, atol = (3e-2, 3e-1) if dtype == jnp.bfloat16 else (2e-3, 1e-2)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(y_ref, np.float32), rtol=rtol, atol=atol
    )
    np.testing.assert_allclose(
        np.asarray(state, np.float32), np.asarray(state_ref, np.float32),
        rtol=rtol, atol=atol,
    )


def test_model_ssd_chunked_matches_ref():
    """The model's XLA chunked SSD == sequential oracle (independent of the
    Pallas kernel)."""
    B, S, H, P, G, N, chunk = 2, 128, 4, 16, 2, 32, 32
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    xh = rand(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(rand(ks[1], (B, S, H), jnp.float32))
    A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=-1.0, maxval=1.0))
    Bv = rand(ks[3], (B, S, G, N), jnp.float32)
    Cv = rand(ks[4], (B, S, G, N), jnp.float32)
    y, state = mamba_lib.ssd_chunked(xh, dt, A, Bv, Cv, chunk)

    r = H // G
    xf = xh.transpose(0, 2, 1, 3).reshape(B * H, S, P)
    dtf = dt.transpose(0, 2, 1).reshape(B * H, S)
    Af = jnp.broadcast_to(A[None], (B, H)).reshape(B * H)
    Bh = jnp.broadcast_to(Bv[:, :, :, None, :], (B, S, G, r, N)).transpose(
        0, 2, 3, 1, 4
    ).reshape(B * H, S, N)
    Ch = jnp.broadcast_to(Cv[:, :, :, None, :], (B, S, G, r, N)).transpose(
        0, 2, 3, 1, 4
    ).reshape(B * H, S, N)
    y_ref, state_ref = ssd_ref.ssd_ref(xf, dtf, Af, Bh, Ch)
    y_ref = y_ref.reshape(B, H, S, P).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(state), np.asarray(state_ref.reshape(B, H, P, N)),
        rtol=1e-4, atol=1e-4,
    )


def test_ssd_chunked_grads_finite_over_long_chunks():
    """Strong decay over a 256-step chunk: exp(cum_t - cum_s) above the
    diagonal overflows, and the mask must keep it out of the backward pass
    (it once turned mamba2-780m's first training step into NaN)."""
    B, S, H, P, G, N, chunk = 1, 256, 2, 4, 1, 4, 256
    ks = jax.random.split(jax.random.PRNGKey(17), 4)
    xh = rand(ks[0], (B, S, H, P), jnp.float32)
    dt = jnp.full((B, S, H), 0.1, jnp.float32)
    A = jnp.full((H,), -16.0, jnp.float32)
    Bv = rand(ks[1], (B, S, G, N), jnp.float32)
    Cv = rand(ks[2], (B, S, G, N), jnp.float32)

    def loss(xh, dt, Bv, Cv):
        y, state = mamba_lib.ssd_chunked(xh, dt, A, Bv, Cv, chunk)
        return jnp.sum(y) + jnp.sum(state)

    grads = jax.grad(loss, argnums=(0, 1, 2, 3))(xh, dt, Bv, Cv)
    for g in grads:
        assert np.isfinite(np.asarray(g)).all()


def test_ssd_decode_step_consistent_with_scan():
    """mamba_decode_step over S steps == chunked scan on the full sequence."""
    B, S, H, P, G, N = 1, 16, 2, 8, 1, 16
    ks = jax.random.split(jax.random.PRNGKey(13), 5)
    xh = rand(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(rand(ks[1], (B, S, H), jnp.float32))
    A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=-1.0, maxval=1.0))
    Bv = rand(ks[3], (B, S, G, N), jnp.float32)
    Cv = rand(ks[4], (B, S, G, N), jnp.float32)
    y_scan, state_scan = mamba_lib.ssd_chunked(xh, dt, A, Bv, Cv, chunk=8)

    # manual per-step recurrence
    state = jnp.zeros((B, H, P, N))
    r = H // G
    ys = []
    for t in range(S):
        Bh = jnp.broadcast_to(Bv[:, t, :, None, :], (B, G, r, N)).reshape(B, H, N)
        Ch = jnp.broadcast_to(Cv[:, t, :, None, :], (B, G, r, N)).reshape(B, H, N)
        decay = jnp.exp(dt[:, t] * A[None])
        state = decay[:, :, None, None] * state + jnp.einsum(
            "bhn,bhp,bh->bhpn", Bh, xh[:, t], dt[:, t]
        )
        ys.append(jnp.einsum("bhn,bhpn->bhp", Ch, state))
    y_seq = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_scan), np.asarray(y_seq), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(state_scan), np.asarray(state), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# int8 TDM payload kernel vs oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,block", [(1024, 256), (4096, 1024), (8192, 512)])
def test_quant_kernel_matches_ref(n, block):
    x = jax.random.normal(jax.random.PRNGKey(n), (n,), jnp.float32) * 3.0
    q, s, _ = q_ops.quantize_payload(x, block=block, interpret=True)
    q_want, s_want = q_ref.quantize_ref(x, block=block)
    np.testing.assert_array_equal(np.asarray(q[:n]), np.asarray(q_want))
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_want), rtol=1e-6)

    back = q_ops.dequantize_payload(q, s, (n,), block=block, interpret=True)
    back_ref = q_ref.dequantize_ref(q_want, s_want, block=block)
    np.testing.assert_allclose(np.asarray(back), np.asarray(back_ref), rtol=1e-6)
    # quantization error bound: blockwise absmax/127
    err = np.abs(np.asarray(back) - np.asarray(x))
    bound = np.repeat(np.asarray(s_want), block) * 0.5 + 1e-7
    assert (err <= bound + 1e-6).all()


@pytest.mark.parametrize("shape", [(33,), (5, 7), (128, 3, 3)])
def test_quant_padding_roundtrip(shape):
    """Non-multiple sizes are padded and exactly un-padded."""
    x = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32)
    qq, ss, shp = q_ops.quantize_payload(x, block=64, interpret=True)
    back = q_ops.dequantize_payload(qq, ss, tuple(shape), block=64, interpret=True)
    assert back.shape == tuple(shape)
    assert np.max(np.abs(np.asarray(back) - np.asarray(x))) < 0.05


@pytest.mark.parametrize("n,block", [(100, 64), (1, 256), (1023, 1024), (1025, 1024)])
def test_quant_kernel_arbitrary_length(n, block):
    """quantize_fwd/dequantize_fwd pad internally: any flat length works and
    matches the blockwise ref, payload comes back exactly n entries long."""
    from repro.kernels.tdm_compress.tdm_compress import dequantize_fwd, quantize_fwd

    x = jax.random.normal(jax.random.PRNGKey(n), (n,), jnp.float32) * 2.0
    q, s = quantize_fwd(x, block=block, interpret=True)
    q_want, s_want = q_ref.quantize_ref(x, block=block)
    assert q.shape == (n,)
    assert s.shape == (-(-n // block),)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q_want))
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_want), rtol=1e-6)
    back = dequantize_fwd(q, s, block=block, interpret=True)
    back_ref = q_ref.dequantize_ref(q_want, s_want, block=block)
    assert back.shape == (n,)
    np.testing.assert_allclose(np.asarray(back), np.asarray(back_ref), rtol=1e-6)


@pytest.mark.parametrize("n,block,w", [(512, 256, 0.25), (1000, 256, 1.0), (77, 64, -0.5)])
def test_dequant_accumulate_matches_ref(n, block, w):
    """Fused receive-side pass acc + w * dequant(q, s) == oracle."""
    ks = jax.random.split(jax.random.PRNGKey(n), 2)
    x = jax.random.normal(ks[0], (n,), jnp.float32) * 3.0
    acc = jax.random.normal(ks[1], (n,), jnp.float32)
    q, s = q_ref.quantize_ref(x, block=block)
    got = q_ops.dequant_accumulate(q, s, acc, w, block=block, interpret=True)
    want = q_ref.dequant_acc_ref(q, s, acc, w, block=block)
    assert got.shape == (n,)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# differential suite: tdm_compress Pallas kernels ≡ jnp oracles, bit for bit
# ---------------------------------------------------------------------------
# Kernel side goes through the jitted q_ops wrappers (interpret mode on
# CPU); oracle side gets its own jit so both see identical XLA arithmetic
# (FMA contraction — see the module docstring).

_ref_quantize = jax.jit(q_ref.quantize_ref, static_argnames=("block",))
_ref_quant_scaled = jax.jit(
    q_ref.quantize_scaled_ref, static_argnames=("block",)
)
_ref_dequant_acc = jax.jit(q_ref.dequant_acc_ref, static_argnames=("block",))
_ref_topk = jax.jit(
    q_ref.topk_sparsify_ref, static_argnums=(1,), static_argnames=("block",)
)
_ref_scatter_acc = jax.jit(q_ref.scatter_acc_ref, static_argnames=("block",))


def _payload(seed: int, n: int, kind: str) -> np.ndarray:
    """Adversarial payload generator: 'normal' random scales, 'ties' holds
    only ±1 (every magnitude equal — selection must break toward the lowest
    index), 'edge' sprinkles NaN/±inf through a normal payload."""
    rng = np.random.default_rng(seed)
    x = (rng.standard_normal(n) * rng.uniform(0.1, 10.0)).astype(np.float32)
    if kind == "ties":
        x = np.where(x >= 0, np.float32(1.0), np.float32(-1.0))
    elif kind == "edge":
        m = rng.random(n)
        x[m < 0.05] = np.nan
        x[(m >= 0.05) & (m < 0.10)] = np.inf
        x[(m >= 0.10) & (m < 0.15)] = -np.inf
    return x


def _assert_topk_equal(x: np.ndarray, k: int, block: int) -> None:
    dense, vals, idxs = q_ops.topk_sparsify(
        jnp.asarray(x), k=k, block=block, interpret=True
    )
    dense_w, vals_w, idxs_w = _ref_topk(jnp.asarray(x), k, block=block)
    nb = -(-x.shape[0] // block)
    assert dense.shape == (x.shape[0],)
    assert vals.shape == idxs.shape == (nb, k)
    # assert_array_equal treats positionally-matching NaNs as equal, so
    # NaN-carrying payloads still compare bit-for-bit
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(dense_w))
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(vals_w))
    np.testing.assert_array_equal(np.asarray(idxs), np.asarray(idxs_w))


@given(
    st_int(1, 2500),
    st_int(0, 128),
    st_choice([128, 256]),
    st_choice(["normal", "ties", "edge"]),
    cases=12,
)
def test_topk_sparsify_differential(n, k, block, kind):
    _assert_topk_equal(_payload(n * 7 + k, n, kind), min(k, block), block)


@pytest.mark.parametrize(
    "n,k,block,kind",
    [
        (1023, 7, 1024, "normal"),     # ragged tail inside one block
        (1025, 5, 1024, "edge"),       # ragged tail spilling a second block
        (256, 0, 256, "normal"),       # k = 0: empty payload, zero dense
        (256, 256, 256, "ties"),       # k = block = n: everything selected
        (64, 64, 256, "edge"),         # k = n < block with NaN/inf
        (1, 1, 64, "normal"),          # single element
        (500, 32, 128, "ties"),        # all-equal magnitudes, ragged
    ],
)
def test_topk_sparsify_adversarial_edges(n, k, block, kind):
    _assert_topk_equal(_payload(n + k, n, kind), k, block)


@given(
    st_int(1, 2500),
    st_int(0, 96),
    st_choice([128, 256]),
    st_choice(["normal", "ties", "edge"]),
    cases=10,
)
def test_scatter_accumulate_differential(n, k, block, kind):
    k = min(k, block)
    x = _payload(n * 13 + k, n, kind)
    rng = np.random.default_rng(n + 1)
    acc = rng.standard_normal(n).astype(np.float32)
    w = np.float32(rng.uniform(-1.5, 1.5))
    _, vals, idxs = _ref_topk(jnp.asarray(x), k, block=block)
    got = q_ops.scatter_accumulate(
        vals, idxs, jnp.asarray(acc), w, block=block, interpret=True
    )
    want = _ref_scatter_acc(vals, idxs, jnp.asarray(acc), w, block=block)
    assert got.shape == (n,)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@given(st_int(1, 3000), st_choice([128, 256, 512]), cases=10)
def test_quantize_scaled_differential(n, block):
    """Shared-scale encode (the quantize-once relay's send side): kernel ==
    oracle exactly, under pmax-style scales ≥ the local blockwise scales."""
    x = _payload(n, n, "normal")
    rng = np.random.default_rng(n + 2)
    scales = np.asarray(q_ref.blockwise_scales_ref(jnp.asarray(x), block=block))
    shared = (scales * rng.uniform(1.0, 3.0, size=scales.shape)).astype(
        np.float32
    )
    got = q_ops.quantize_scaled(
        jnp.asarray(x), jnp.asarray(shared), block=block, interpret=True
    )
    want = _ref_quant_scaled(jnp.asarray(x), jnp.asarray(shared), block=block)
    assert got.dtype == jnp.int8 and got.shape == (n,)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@given(st_int(1, 3000), st_choice([128, 256, 512]), cases=10)
def test_quantize_differential_bitwise(n, block):
    x = _payload(n * 3, n, "normal")
    q, s, _ = q_ops.quantize_payload(jnp.asarray(x), block=block, interpret=True)
    q_w, s_w = _ref_quantize(jnp.asarray(x), block=block)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q_w))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(s_w))


@given(st_int(1, 2500), st_choice([128, 256]), st_choice([1, 6]), cases=10)
def test_dequant_accumulate_int16_differential(n, block, sources):
    """Integer-domain relay sums: int16 q (up to ±127×sources, the
    quantize-once relay's wire format) dequantize+accumulate bit-for-bit."""
    rng = np.random.default_rng(n * 5 + sources)
    lim = 127 * sources
    q = rng.integers(-lim, lim + 1, size=n).astype(np.int16)
    nb = -(-n // block)
    s = rng.uniform(1e-4, 0.5, size=nb).astype(np.float32)
    acc = rng.standard_normal(n).astype(np.float32)
    w = np.float32(rng.uniform(-1.0, 1.0))
    got = q_ops.dequant_accumulate(
        jnp.asarray(q), jnp.asarray(s), jnp.asarray(acc), w,
        block=block, interpret=True,
    )
    want = _ref_dequant_acc(
        jnp.asarray(q), jnp.asarray(s), jnp.asarray(acc), w, block=block
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
