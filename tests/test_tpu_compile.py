"""Compile the ``tdm_compress`` kernels, and the Pallas kernels of the
benchmark's layers (the MoE's grouped matmuls, the training attention's
splash kernels), for a described TPU v5e chip.

Interpret mode (``tests/test_kernels.py``) checks what the kernels compute;
it cannot see what the TPU's Mosaic compiler refuses — tile shapes that
break the (8, 128) / (32, 128) layout rules, or tiles that overflow VMEM.
These tests lower every kernel at the length of one node's fused
``mamba2-780m`` buffer for a ``v5e:2x2`` topology that is described, not
attached, and check that the kernel survived as a ``tpu_custom_call``
named after its entry point (``tdm_quantize``, ...), as a trace shows it;
the layers' kernels are lowered inside one layer's forward and backward
at ``nemotron-3-nano-30b-a3b``'s published widths and the benchmark's
2 x 8192 tokens.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker imports
this file.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.tdm_compress import tdm_compress as kern

# one node's fused mamba2-780m parameter buffer (configs/archs.py widths)
N_ELEMS = 780_148_992
BLOCK = 1024
NB = -(-N_ELEMS // BLOCK)
K = 8  # per-block top-k budget


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _arg(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


CASES = {
    "quantize": (
        "tdm_quantize",
        functools.partial(kern.quantize_fwd, block=BLOCK),
        [((N_ELEMS,), jnp.float32)],
    ),
    "dequantize": (
        "tdm_dequantize",
        functools.partial(kern.dequantize_fwd, block=BLOCK),
        [((N_ELEMS,), jnp.int8), ((NB,), jnp.float32)],
    ),
    "dequant_accumulate": (
        "tdm_dequant_acc",
        functools.partial(kern.dequant_accumulate_fwd, block=BLOCK),
        [
            ((N_ELEMS,), jnp.int8),
            ((NB,), jnp.float32),
            ((N_ELEMS,), jnp.float32),
            ((), jnp.float32),
        ],
    ),
    "quantize_scaled": (
        "tdm_quantize_scaled",
        functools.partial(kern.quantize_scaled_fwd, block=BLOCK),
        [((N_ELEMS,), jnp.float32), ((NB,), jnp.float32)],
    ),
    "topk_sparsify": (
        "tdm_topk",
        functools.partial(kern.topk_sparsify_fwd, k=K, block=BLOCK),
        [((N_ELEMS,), jnp.float32)],
    ),
    "scatter_accumulate": (
        "tdm_scatter_acc",
        functools.partial(kern.scatter_accumulate_fwd, block=BLOCK),
        [
            ((NB, K), jnp.float32),
            ((NB, K), jnp.int32),
            ((N_ELEMS,), jnp.float32),
            ((), jnp.float32),
        ],
    ),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_compile_cache):
    kernel, fn, shapes = CASES[name]
    args = [_arg(s, d, one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    # the kernel is named after its entry point, in the custom-call's
    # instruction name and in its op_name: what a trace shows of it
    (call,) = [l for l in text.splitlines() if "tpu_custom_call" in l]
    assert call.split("=")[0].split()[-1].lstrip("%").split(".")[0] == kernel
    assert f"/{kernel}/pallas_call" in call


def test_moe_grouped_matmuls_compile_for_v5e(one_chip, no_compile_cache, monkeypatch):
    """The held experts of one ``nemotron-3-nano-30b-a3b`` MoE layer at its
    published widths (D 2688, expert width 1856, top-6 of 128, 8 held) over
    the benchmark's 16,384 tokens, forward and backward under the round's
    remat policy, take the TPU path of ``moe.grouped_matmul``: megablox
    ``gmm`` and ``tgmm`` kernels, which a trace names after them
    (``moe.expert_kernel_ms`` reads them), in one branch per size of the
    buffer's ladder, each under its ``rows_<n>`` scope."""
    import collections
    import dataclasses
    import re

    from repro.configs import archs
    from repro.models import moe, transformer

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the described chip
    cfg = archs.get("nemotron-3-nano-30b-a3b")
    cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, held=8))
    params = jax.eval_shape(lambda k: moe.init_dropless(k, cfg)[0], jax.random.PRNGKey(0))
    params = jax.tree.map(lambda x: _arg(x.shape, x.dtype, one_chip), params)
    x = _arg((2, 8192, cfg.d_model), jnp.bfloat16, one_chip)

    def layer(p, x):
        return x + moe.moe_dropless(p, x, cfg)[0]

    def loss(p, x):
        # the gradient needs the layer's output, as the next layer's does
        y = transformer.remat_layer(layer, cfg)(p, x)
        return jnp.sum(jnp.sin(y.astype(jnp.float32)))

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(params, x).compile().as_text()
    kernels = collections.defaultdict(list)
    for line in text.splitlines():
        if "tpu_custom_call" in line:
            (rows,) = set(re.findall(r"\brows_(\d+)\b", line))
            kernels[int(rows)].append(line.split("=")[0].split()[-1].lstrip("%").split(".")[0])
    ladder = moe.size_ladder(2 * 8192, cfg)
    assert ladder == (12288, 24576, 49152, 98304)
    # in each size's branch: two matmuls forward, and in the backward the
    # same two again (the layer is rematerialised) with an input gradient
    # (gmm) and a weight gradient (tgmm) each, as the whole buffer took
    # without the ladder
    assert {n: sorted(names) for n, names in kernels.items()} == {
        n: ["gmm"] * 6 + ["tgmm"] * 2 for n in ladder
    }
    # one switch forward, returning the layer's output alone, and one
    # backward: no branch writes the full buffer's rows for the backward
    switches = [l.split("conditional(")[0] for l in text.splitlines() if " conditional(" in l]
    assert len(switches) == 2
    assert not any(f"[{ladder[-1]}," in out for out in switches), switches


def test_attention_kernels_compile_for_v5e(one_chip, no_compile_cache, monkeypatch):
    """One ``nemotron-3-nano-30b-a3b`` attention layer at the benchmark's
    shape (B 2, S 8192, 32 query heads sharing 2 KV heads of 128, bf16,
    causal), forward and backward under the round's remat policy, takes the
    TPU path of ``flash_attention_train``: the splash kernels, named after
    their entry points as a trace shows them (``attention.kernel_ms`` reads
    them), a forward twice (the output, and its recompute for the backward)
    and one ``dkv`` and one ``dq`` pass. No f32 score tile of the blocked
    pure-JAX path (f32[..., 512, 1024], one q block by one kv block) is left
    in the module."""
    import re

    from repro.configs import archs
    from repro.models import transformer

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the described chip
    cfg = archs.get("nemotron-3-nano-30b-a3b")
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (32, 2, 128)
    desc = transformer.LayerDesc("attn")
    params = jax.eval_shape(
        lambda k: transformer.init_attention(k, cfg)[0], jax.random.PRNGKey(0)
    )
    params = jax.tree.map(lambda x: _arg(x.shape, x.dtype, one_chip), params)
    x = _arg((2, 8192, cfg.d_model), jnp.bfloat16, one_chip)

    def layer(p, x):
        return x + transformer.attn_train(p, x, None, cfg, desc)

    def loss(p, x):
        # the gradient needs the layer's output, as the next layer's does
        y = transformer.remat_layer(layer, cfg)(p, x)
        return jnp.sum(jnp.square(y.astype(jnp.float32)))

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(params, x).compile().as_text()
    kernels = sorted(
        line.split("=")[0].split()[-1].lstrip("%").split(".")[0]
        for line in text.splitlines() if "tpu_custom_call" in line
    )
    assert kernels == [
        "splash_mha_dkv_no_residuals",
        "splash_mha_dq_no_residuals",
        "splash_mha_fwd_residuals",
        "splash_mha_fwd_residuals",
    ]
    assert not re.search(r"f32\[[\d,]*512,1024\]", text)
