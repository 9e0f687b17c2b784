"""Model registry: configs -> callable bundles + dry-run input specs.

``bundle(cfg)`` wraps the functional model (init / train loss / prefill /
decode) behind one object; ``input_specs(cfg, shape)`` produces the
ShapeDtypeStruct stand-ins for every model input of a cell — weak-type
correct, shardable, zero allocation — consumed by launch/dryrun.py.
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import moe, transformer
from repro.models.config import ModelConfig, ShapeConfig
from repro.models.layers import dtype_of


@dataclass(frozen=True)
class ModelBundle:
    cfg: ModelConfig

    # ------------------------------------------------------------- factories
    def init(self, key) -> Tuple[Any, Any]:
        return transformer.init_params(key, self.cfg)

    def param_specs_tree(self):
        """(eval-shaped params, logical specs) with no allocation."""
        return param_specs(self.cfg)

    # ------------------------------------------------------------ step fns
    def loss_fn(self, params, batch):
        return transformer.loss_fn(params, batch, self.cfg)

    def step_gauges(self, batch: int, seq: int) -> Dict[str, int]:
        """Gauges of a training step over ``batch`` x ``seq`` tokens that the
        model's layers define: ``attention.kernel_layers``, the attention
        calls that run the fused Pallas kernel
        (:func:`repro.models.transformer.attention_kernel_layers`), and a
        dropless MoE's (:func:`repro.models.moe.step_gauges`)."""
        return {
            "attention.kernel_layers": transformer.attention_kernel_layers(self.cfg, batch, seq),
            **moe.step_gauges(batch * seq, self.cfg),
        }

    def prefill_fn(self, params, batch, max_len: int):
        return transformer.prefill(
            params,
            batch["tokens"],
            self.cfg,
            max_len,
            positions=batch.get("positions"),
            enc_embeds=batch.get("enc_embeds"),
        )

    def decode_fn(self, params, cache, batch):
        return transformer.decode_step(
            params, cache, batch["token"], self.cfg,
            positions=batch.get("positions"),
        )

    def init_cache(self, batch: int, max_len: int):
        return transformer.init_cache(self.cfg, batch, max_len)

    def cache_logical_specs(self):
        return {
            "pos": (),
            "units": transformer.cache_logical_specs(self.cfg),
        }


@functools.lru_cache(maxsize=64)
def param_specs(cfg: ModelConfig):
    """(param ShapeDtypeStructs, logical-axis specs) with ZERO allocation.

    The specs tree is plain Python built during tracing; we capture it as a
    side effect of ``eval_shape`` (strings can't be eval_shape outputs).
    """
    captured = {}

    def init_shapes():
        p, s = transformer.init_params(jax.random.PRNGKey(0), cfg)
        captured["specs"] = s
        return p

    shapes = jax.eval_shape(init_shapes)
    return shapes, captured["specs"]


def bundle(cfg: ModelConfig) -> ModelBundle:
    return ModelBundle(cfg)


# ---------------------------------------------------------------------------
# input specs per (cfg, shape-cell)
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for the step function's data inputs."""
    B = shape.global_batch
    S = shape.seq_len
    i32 = jnp.int32
    cdt = dtype_of(cfg.compute_dtype)
    if shape.kind == "train":
        specs = {
            "tokens": jax.ShapeDtypeStruct((B, S), i32),
            "labels": jax.ShapeDtypeStruct((B, S), i32),
        }
        if cfg.mrope_sections is not None:
            specs["positions"] = jax.ShapeDtypeStruct((B, S, 3), i32)
        if cfg.enc_dec:
            specs["enc_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.enc_frames, cfg.d_model), cdt
            )
        return specs
    if shape.kind == "prefill":
        specs = {"tokens": jax.ShapeDtypeStruct((B, S), i32)}
        if cfg.mrope_sections is not None:
            specs["positions"] = jax.ShapeDtypeStruct((B, S, 3), i32)
        if cfg.enc_dec:
            specs["enc_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.enc_frames, cfg.d_model), cdt
            )
        return specs
    # decode: one new token against a seq_len cache
    specs = {"token": jax.ShapeDtypeStruct((B, 1), i32)}
    if cfg.mrope_sections is not None:
        specs["positions"] = jax.ShapeDtypeStruct((B, 1, 3), i32)
    return specs


def cache_specs(cfg: ModelConfig, shape: ShapeConfig) -> Any:
    """ShapeDtypeStruct tree for the decode cache of this cell."""
    return jax.eval_shape(
        lambda: transformer.init_cache(cfg, shape.global_batch, shape.seq_len)
    )
