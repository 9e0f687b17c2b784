"""The nemotron-h hybrid (``nemotron-3-nano-30b-a3b``): single-mixer layers
in a pattern, Mamba-2 with grouped gated norm, NoPE grouped-query attention,
and a sigmoid-routed, dropless MoE that holds a share of its router's
experts, checked on the CPU at a tiny size against the plain reference the
benchmark compares with (``bench/configs/nemotron-3-nano-30b-a3b.py``); and
the round programs of the benchmark's other configurations, unchanged by it.
"""

import copy
import dataclasses
import hashlib
import math
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import harness, traffic  # noqa: E402
from repro.configs import archs  # noqa: E402
from repro.core.relation import Relation  # noqa: E402
from repro.launch import fl_train  # noqa: E402
from repro.launch import mesh as mesh_lib  # noqa: E402
from repro.models import mamba2, moe, registry  # noqa: E402
from repro.models.layers import rmsnorm  # noqa: E402
from repro.optim import adamw  # noqa: E402

NAME = "nemotron-3-nano-30b-a3b"
B, S = 2, 32


def tiny_config():
    """The configuration file at a test's size: pattern MEM*E, 16 routed
    experts of which 4 are held, top-4, 2 B/C groups, chunks of 8, float32
    compute. Weights are drawn wider than the published init (std 0.2) so
    that every layer moves the output by more than round-off."""
    conf, model = harness.load_config(NAME)
    conf = copy.deepcopy(conf)
    conf.update(
        hidden_size=64, hybrid_override_pattern="MEM*E", num_hidden_layers=5,
        n_routed_experts=4, first_held=0, num_experts_per_tok=4, n_groups=2,
        chunk_size=8, mamba_num_heads=4, mamba_head_dim=16, ssm_state_size=16,
        num_attention_heads=8, num_key_value_heads=2, head_dim=16,
        moe_intermediate_size=32, moe_shared_expert_intermediate_size=48,
        vocab_size=128,
    )
    conf["published"] = dict(conf["published"], n_routed_experts=16)
    conf["assumed"] = dict(conf["assumed"], compute_dtype="float32",
                           initializer_range=0.2)
    return conf, model


def program_cfg(conf, model):
    return model.program_config(conf, archs).replace(
        attn_block_q=8, attn_block_k=8, loss_chunk=16
    )


def _batch(vocab, seed=0, seq=S):
    rng = np.random.default_rng(seed)
    stream = rng.integers(0, vocab, (B, seq + 1))
    return {"tokens": jnp.asarray(stream[:, :-1], jnp.int32),
            "labels": jnp.asarray(stream[:, 1:], jnp.int32)}


def _rel_gap(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


@pytest.fixture(scope="module")
def tiny():
    conf, model = tiny_config()
    cfg = program_cfg(conf, model)
    params = model.init(jax.random.PRNGKey(3), conf)
    batch = _batch(conf["vocab_size"])
    prog = jax.jit(jax.value_and_grad(
        lambda p: registry.bundle(cfg).loss_fn(p, batch)[0]))(params)
    return conf, model, params, batch, prog


def _reference(model, conf, params, batch, cast=lambda a: a):
    old = model.QUERY_BLOCK
    model.QUERY_BLOCK = 8  # several query blocks at the test's length
    try:
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(
                lambda p: model.loss(p, batch, conf, cast)))(params)
    finally:
        model.QUERY_BLOCK = old


# The program in float32 against the float32 reference. The two sum in
# different orders (the chunked SSD against its quadratic form, blocked
# online-softmax attention against softmax of the scores, grouped matmuls
# against a dense loop over experts), so they agree to float32 round-off:
# relative 1e-5 on the loss (read 1e-7 and below), 1e-4 on each gradient
# leaf by norm of the difference (read 1e-6 and below). bfloat16 rounding of
# the reference's matmul operands (relative 2^-9) is far outside both.
LOSS_RTOL, GRAD_RTOL = 1e-5, 1e-4


def _gaps(prog, ref):
    (l0, g0), (l1, g1) = prog, ref
    loss_gap = abs(float(l0) - float(l1)) / abs(float(l1))
    grad_gaps = {
        jax.tree_util.keystr(path): _rel_gap(a, b)
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g0), jax.tree.leaves(g1))
        if float(jnp.linalg.norm(b)) > 0
    }
    return loss_gap, grad_gaps


def test_program_matches_reference(tiny):
    conf, model, params, batch, prog = tiny
    loss_gap, grad_gaps = _gaps(prog, _reference(model, conf, params, batch))
    assert loss_gap <= LOSS_RTOL, loss_gap
    worst = max(grad_gaps, key=grad_gaps.get)
    assert grad_gaps[worst] <= GRAD_RTOL, (worst, grad_gaps[worst])
    # every leaf is compared but each MoE layer's correction bias, which
    # only ranks the choice and has no gradient
    n_moe = conf["hybrid_override_pattern"].count("E")
    assert len(grad_gaps) == len(jax.tree.leaves(params)) - n_moe


def test_program_on_a_ladder_matches_reference(tiny):
    """At 2 x 128 tokens each MoE layer's buffer has two sizes (512 and
    1,024 rows), so the layers run the ladder's switch inside the program's
    layer scan and checkpoints, forward and backward: the program still
    matches the reference."""
    conf, model, params, _, _ = tiny
    cfg = program_cfg(conf, model)
    batch = _batch(conf["vocab_size"], seq=128)
    assert len(moe.size_ladder(B * 128, cfg)) == 2
    prog = jax.jit(jax.value_and_grad(
        lambda p: registry.bundle(cfg).loss_fn(p, batch)[0]))(params)
    loss_gap, grad_gaps = _gaps(prog, _reference(model, conf, params, batch))
    assert loss_gap <= LOSS_RTOL, loss_gap
    worst = max(grad_gaps, key=grad_gaps.get)
    assert grad_gaps[worst] <= GRAD_RTOL, (worst, grad_gaps[worst])


def test_bf16_reference_fails_the_tolerances(tiny):
    conf, model, params, batch, prog = tiny
    bf16 = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
    loss_gap, grad_gaps = _gaps(prog, _reference(model, conf, params, batch, bf16))
    assert loss_gap > LOSS_RTOL or max(grad_gaps.values()) > GRAD_RTOL


def _moe_setup(n_experts=16, top_k=4, held=4, seed=0, T=24, D=32):
    conf, model = tiny_config()
    conf.update(hidden_size=D, num_experts_per_tok=top_k, n_routed_experts=held)
    conf["published"] = dict(conf["published"], n_routed_experts=n_experts)
    cfg = model.program_config(conf, archs)
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    F, Fs = conf["moe_intermediate_size"], conf["moe_shared_expert_intermediate_size"]
    p = {
        "router": jax.random.normal(ks[0], (D, n_experts)),
        "router_bias": 0.1 * jax.random.normal(ks[1], (n_experts,)),
        "wi": jax.random.normal(ks[2], (n_experts, D, F)) * D ** -0.5,
        "wo": jax.random.normal(ks[3], (n_experts, F, D)) * F ** -0.5,
        "shared": {"wi": jax.random.normal(ks[4], (D, Fs)) * D ** -0.5,
                   "wo": jax.random.normal(ks[5], (Fs, D)) * Fs ** -0.5},
    }
    x = jax.random.normal(ks[6], (1, T, D))
    return conf, model, cfg, p, x


def _share(cfg, p, first, held):
    cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, held=held, first_held=first))
    return cfg, dict(p, wi=p["wi"][first:first + held], wo=p["wo"][first:first + held])


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips of an expert-parallel layer, each holding 4 of the 16
    experts: their outputs, with the shared expert (which each computes
    alike) counted once, add up to the layer that holds all 16."""
    conf, model, cfg, p, x = _moe_setup()
    run = lambda c, q: moe.moe_dropless(q, x, c)[0]
    shares = [run(*_share(cfg, p, first, 4)) for first in (0, 4, 8, 12)]
    shared = moe.mlp_apply(p["shared"], x, cfg)
    whole = run(*_share(cfg, p, 0, 16))
    np.testing.assert_allclose(sum(shares) - 3 * shared, whole, rtol=1e-5, atol=1e-5)
    # and the uncut layer is the reference's
    d = model.dims(dict(conf, n_routed_experts=16))
    with jax.default_matmul_precision("highest"):
        ref = model.moe(p, x, d, lambda a: a)
    np.testing.assert_allclose(whole, ref, rtol=1e-5, atol=1e-5)


def test_dropless_under_imbalance():
    """The correction bias sends every token to the same four held experts
    (every assignment lands here, a full buffer of T x top_k rows): nothing
    is dropped, where a capacity of 1.25 x the even share would keep a
    quarter of the assignments."""
    conf, model, cfg, p, x = _moe_setup(seed=1)
    T = x.shape[1]
    p = dict(p, router_bias=jnp.zeros((16,)).at[4:8].set(10.0))
    cfg, q = _share(cfg, p, 4, 4)
    top_e, _ = moe.route(q, x.reshape(T, -1), cfg)
    assert set(np.unique(np.asarray(top_e))) == {4, 5, 6, 7}
    assert moe.buffer_rows(T, cfg) == T * 4
    out = moe.moe_dropless(q, x, cfg)[0]
    d = dict(model.dims(conf), held=4, first=4)
    with jax.default_matmul_precision("highest"):
        ref = model.moe(q, x, d, lambda a: a)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def _undefined_past_groups(a, w, sizes):
    """A grouped matmul that, as the TPU kernels do, leaves the rows past
    the groups undefined (here NaN) in its output and in its lhs gradient,
    and reads only the groups' rows for its weight gradient."""

    def fill(x, ref_sizes):
        valid = jnp.arange(x.shape[0]) < jnp.sum(ref_sizes)
        return jnp.where(valid[:, None], x, jnp.nan)

    @jax.custom_vjp
    def f(a, w, sizes):
        return fill(jax.lax.ragged_dot(a, w, sizes), sizes)

    def fwd(a, w, sizes):
        return f(a, w, sizes), (a, w, sizes)

    def bwd(res, g):
        a, w, sizes = res
        g = jnp.where((jnp.arange(g.shape[0]) < jnp.sum(sizes))[:, None], g, 0)
        _, vjp = jax.vjp(lambda a, w: jax.lax.ragged_dot(a, w, sizes), a, w)
        da, dw = vjp(g)
        return fill(da, sizes), dw, np.zeros(sizes.shape, jax.dtypes.float0)

    f.defvjp(fwd, bwd)
    return f(a, w, sizes)


# Where one MoE layer's held assignments land in the ladder of buffer sizes
# (512, 1024, 2048, 4096 rows for 1024 tokens, top-4 of 64 experts, 4 held):
# the correction bias raises some held experts above every other, so each
# token sends them its assignments. "single": a layer whose ladder is one
# size (24 tokens of 16 experts: 512 rows reach the bound of 96).
LADDER_CASES = {
    "single": (dict(seed=2), 0, 0),
    "first": (dict(n_experts=64, seed=2, T=1024), 0, 0),
    "middle": (dict(n_experts=64, seed=2, T=1024), 1, 2),
    "full": (dict(n_experts=64, seed=2, T=1024), 4, 3),
}


def _ladder_case(case):
    """(conf, model, cfg, the share's params, x) of a case, the layer
    holding experts 4-7, its held assignments checked to take the case's
    size of the ladder."""
    setup, raised, index = LADDER_CASES[case]
    conf, model, cfg, p, x = _moe_setup(**setup)
    E, T = p["router"].shape[1], x.shape[1]
    if raised:
        p = dict(p, router_bias=jnp.zeros((E,)).at[4:4 + raised].set(10.0))
    cfg, q = _share(cfg, p, 4, 4)
    top_e, _ = moe.route(q, x.reshape(T, -1), cfg)
    count = int(jnp.sum((top_e >= 4) & (top_e < 8)))
    ladder = moe.size_ladder(T, cfg)
    assert sum(count > n for n in ladder[:-1]) == index, (count, ladder)
    return conf, model, cfg, q, x


def _sin_loss(cfg):
    return lambda q, x: jnp.sum(jnp.sin(moe.moe_dropless(q, x, cfg)[0]))


@pytest.mark.parametrize("case", ["first", "middle", "full"])
def test_ladder_matches_the_full_buffer(case, monkeypatch):
    """Whichever size of the ladder holds the held assignments, the output
    and the gradients (params and x) are those of the whole buffer of
    tokens x min(top_k, held) rows, and the uncut reference's."""
    conf, model, cfg, q, x = _ladder_case(case)
    loss = _sin_loss(cfg)
    out = moe.moe_dropless(q, x, cfg)[0]
    grads = jax.grad(loss, argnums=(0, 1))(q, x)
    monkeypatch.setattr(moe, "size_ladder", lambda T, cfg: (moe.buffer_rows(T, cfg),))
    np.testing.assert_allclose(out, moe.moe_dropless(q, x, cfg)[0], rtol=1e-6, atol=1e-6)
    whole = jax.grad(loss, argnums=(0, 1))(q, x)
    d = dict(model.dims(conf), held=4, first=4)
    with jax.default_matmul_precision("highest"):
        ref_out = model.moe(q, x, d, lambda a: a)
        ref = jax.grad(lambda q, x: jnp.sum(jnp.sin(model.moe(q, x, d, lambda a: a))),
                       argnums=(0, 1))(q, x)
    np.testing.assert_allclose(out, ref_out, rtol=1e-5, atol=1e-5)
    for (path, a), b, c in zip(jax.tree_util.tree_leaves_with_path(grads),
                               jax.tree.leaves(whole), jax.tree.leaves(ref)):
        assert _rel_gap(a, b) <= 1e-6, (jax.tree_util.keystr(path), _rel_gap(a, b))
        assert _rel_gap(a, c) <= GRAD_RTOL, (jax.tree_util.keystr(path), _rel_gap(a, c))


@pytest.mark.parametrize("case", sorted(LADDER_CASES))
def test_rows_past_the_groups_stay_out(case, monkeypatch):
    """The buffer's rows past the held assignments belong to no group; what
    the kernel leaves in them reaches neither the output nor a gradient,
    in whichever size of the ladder the layer runs."""
    conf, model, cfg, q, x = _ladder_case(case)
    loss = _sin_loss(cfg)
    clean = jax.value_and_grad(loss, argnums=(0, 1))(q, x)
    monkeypatch.setattr(moe, "grouped_matmul", _undefined_past_groups)
    dirty = jax.value_and_grad(loss, argnums=(0, 1))(q, x)
    for a, b in zip(jax.tree.leaves(dirty), jax.tree.leaves(clean)):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("tokens,held,want", [
    (16384, 8, (12288, 24576, 49152, 98304)),  # solo8k: 2 x 8192 tokens, 8 of 128 held
    (1000, 8, (1024, 2048, 4096, 6000)),       # the last size is the bound, off the tiles
    (16384, 128, (98304,)),                    # every expert held: the first is the bound
    (32, 8, (192,)),                           # a few tokens: one tile is past the bound
])
def test_size_ladder(tokens, held, want):
    """The buffer's sizes come from shapes alone: they increase, each but a
    bound is a multiple of the grouped-matmul tile, the first is twice the
    held experts' even share rounded up to it, and the last is the bound."""
    conf, model = harness.load_config(NAME)
    cfg = model.program_config(conf, archs)
    cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, held=held))
    ladder = moe.size_ladder(tokens, cfg)
    assert ladder == want
    assert list(ladder) == sorted(set(ladder))
    assert ladder[-1] == moe.buffer_rows(tokens, cfg)
    assert all(n % moe.GMM_TM == 0 for n in ladder[:-1])
    even = tokens * cfg.moe.top_k * held / cfg.moe.n_experts
    assert ladder[0] == min(moe.GMM_TM * math.ceil(2 * even / moe.GMM_TM), ladder[-1])


@pytest.mark.parametrize("groups", [1, 2, 8])
def test_grouped_gated_norm(groups):
    """Each group of d_inner / G channels of y * silu(z) is normalised on
    its own; one group is the plain RMSNorm."""
    cfg = archs.smoke_cfg(archs.get(NAME)).replace(compute_dtype="float32")
    cfg = cfg.replace(mamba=dataclasses.replace(cfg.mamba, n_groups=groups))
    di = 64
    rng = np.random.default_rng(groups)
    y, z = rng.standard_normal((2, 3, 5, di)).astype(np.float32)
    scale = rng.standard_normal(di).astype(np.float32)
    got = mamba2.gated_norm(jnp.asarray(y), jnp.asarray(z), jnp.asarray(scale), cfg)
    gated = y * z / (1 + np.exp(-z))
    want = np.concatenate([
        g / np.sqrt(np.mean(g * g, axis=-1, keepdims=True) + cfg.norm_eps)
        for g in np.split(gated, groups, axis=-1)
    ], axis=-1) * (1 + scale)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    if groups == 1:
        y, z = jnp.asarray(y), jnp.asarray(z)
        plain = rmsnorm(y * jax.nn.silu(z), jnp.asarray(scale), cfg.norm_eps)
        np.testing.assert_array_equal(got, plain)


def test_param_counts():
    """The published model has 31.58B parameters (31.6B in its card); the
    benchmark's cut (one period, 8 held experts, an eighth of the
    vocabulary) has 528,093,120, as many as the reference's weights."""
    assert archs.get(NAME).param_count() == 31_577_940_288
    conf, model = harness.load_config(NAME)
    cfg = model.program_config(conf, archs)
    shapes = jax.eval_shape(lambda k: model.init(k, conf), jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert cfg.param_count() == n == 528_093_120
    program = jax.eval_shape(lambda k: registry.bundle(cfg).init(k)[0], jax.random.PRNGKey(0))
    assert jax.tree.structure(program) == jax.tree.structure(shapes)
    assert all(a.shape == b.shape for a, b in
               zip(jax.tree.leaves(program), jax.tree.leaves(shapes)))


# sha256 of each solo round's lowered module, locations stripped, before the
# hybrid's layers came in: the change leaves these programs as they were
SOLO_MODULES = {
    ("mamba2-780m", "solo"):
        "55a5d60c6fdec16e99b89340e8db41c1cba45604803ba6e593015d6c47f093d9",
    ("whisper-base", "solo.b64x448"):
        "71a4eb60271cb04550a2b6d4f6e447c090142d473b9d77475a32658211d4c5ac",
}


def solo_module(config: str, mix_name: str) -> str:
    """The solo round's lowered module at the configuration's published
    widths and its traffic's batch, from abstract shapes, without locations."""
    conf, model = harness.load_config(config)
    mix = traffic.load(mix_name)
    cfg = model.program_config(conf, archs)
    opt = dict(conf["assumed"]["optimizer"])
    opt.pop("name")
    opt_cfg = adamw.OptConfig(dtype=conf["assumed"]["opt_dtype"], **opt)
    fn = fl_train.build_fl_round(
        cfg, opt_cfg, mesh_lib.make_mesh((1,), ("data",)), 1,
        fl_train.FLConfig(mode="tdm", local_steps=1, compression=mix["compression"]),
        Relation.from_edges([], nodes=range(1)),
    )
    params = jax.eval_shape(lambda k: model.init(k, conf), jax.ShapeDtypeStruct((2,), jnp.uint32))
    state = {"params": params,
             "opt": jax.eval_shape(lambda p: adamw.init_opt_state(p, opt_cfg), params),
             "step": jax.ShapeDtypeStruct((), jnp.int32)}
    state = jax.tree.map(lambda x: jax.ShapeDtypeStruct((1,) + x.shape, x.dtype), state)
    features = getattr(model, "encoder_features", lambda c: None)(conf)
    pool = traffic.make_pool(dict(mix, pool_rounds=1), conf["vocab_size"], 0, features)
    batch = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), pool[0])
    text = fn.lower(state, batch).as_text()
    text = re.sub(r"\s*loc\([^\n]*?\)(?=\s*$)", "", text, flags=re.M)
    return "\n".join(line for line in text.splitlines() if not line.startswith("#loc"))


@pytest.mark.parametrize("config,mix_name", sorted(SOLO_MODULES))
def test_solo_round_modules_unchanged(config, mix_name):
    text = solo_module(config, mix_name)
    assert hashlib.sha256(text.encode()).hexdigest() == SOLO_MODULES[config, mix_name]


def test_expert_kernel_reader_sums_the_grouped_matmuls():
    """``moe.expert_kernel_ms`` sums the device time of the grouped-matmul
    kernels (``gmm``, ``tgmm``) per round and chip; a program without them
    (one with no dropless MoE) reads nothing."""
    from bench import trace

    reader = harness.load_module(
        ROOT / "bench" / "metrics" / "moe.expert_kernel_ms.py", "expert_kernel_ms"
    )
    call = 'custom-call(%a, %b), custom_call_target="tpu_custom_call"'
    ops = [
        trace.Op(0, 2e6, f"%gmm.84 = bf16[98304,1920]{{1,0}} {call}"),
        trace.Op(2e6, 2.5e6, f"%tgmm.7 = bf16[8,2688,1920]{{2,1,0}} {call}"),
        trace.Op(3e6, 9e6, "%fusion.1 = f32[8]{0} fusion(%d), kind=kLoop"),
        trace.Op(9e6, 9.5e6, f"%tdm_quantize.1 = s8[4,8,128]{{2,1,0}} {call}"),
    ]
    ctx = {"trace": trace.Trace(chips={"/device:TPU:0": ops}, host=[]),
           "lo": 0, "hi": 1e7, "rounds": 2}
    assert reader.read(ctx) == pytest.approx(1.25)
    ctx["trace"] = trace.Trace(chips={"/device:TPU:0": ops[2:]}, host=[])
    assert reader.read(ctx) is None
