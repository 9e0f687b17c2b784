"""The one traffic generator: reads a traffic mix (``bench/traffic/<name>.json``)
and makes, from the seed, the pool of batches that the FL rounds of a cell
consume.

A mix gives the nodes and the relation of every slot, the compression of
the exchange, the local steps per round, the batch and sequence length per
node and step, and how many rounds of distinct batches the pool holds. Every
seed gets the same sizes; only the token ids (and, for an encoder-decoder
model, the encoder features) change with it. Round ``r`` of a run reads
pool entry ``r % pool_rounds``, so the first ``pool_rounds`` rounds see
rows that all differ.
"""

from __future__ import annotations

import json
import pathlib

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
REQUIRED = (
    "runner", "nodes", "edges", "compression", "local_steps", "batch", "seq",
    "pool_rounds", "trace_seconds",
)


def load(name: str) -> dict:
    mix = json.loads((HERE / "traffic" / f"{name}.json").read_text())
    missing = [k for k in REQUIRED if k not in mix]
    if missing:
        raise ValueError(f"traffic {name}: missing {missing}")
    for a, b in mix["edges"]:
        if not (0 <= a < mix["nodes"] and 0 <= b < mix["nodes"]) or a == b:
            raise ValueError(f"traffic {name}: bad edge {(a, b)}")
    return mix


def seed_words(seed: int) -> np.ndarray:
    """Two 32-bit words from a seed of any size (for ``jax.random``)."""
    return np.random.SeedSequence(int(seed)).generate_state(2, dtype=np.uint32)


def make_pool(mix: dict, vocab: int, seed: int, enc_shape=None) -> list:
    """``pool_rounds`` batches, each ``{"tokens", "labels"[, "enc_embeds"]}``
    with leading axes ``(nodes, local_steps, batch)``: token ids uniform
    over the vocabulary, labels the next token of the same stream, and
    (when ``enc_shape = (frames, width)``) encoder features N(0, 1)."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0x7AFF1C]))
    lead = (mix["pool_rounds"], mix["nodes"], mix["local_steps"], mix["batch"])
    stream = rng.integers(0, vocab, lead + (mix["seq"] + 1,), dtype=np.int32)
    feats = None
    if enc_shape is not None:
        feats = rng.standard_normal(lead + tuple(enc_shape), dtype=np.float32)
    pool = []
    for r in range(mix["pool_rounds"]):
        batch = {
            "tokens": np.ascontiguousarray(stream[r, ..., :-1]),
            "labels": np.ascontiguousarray(stream[r, ..., 1:]),
        }
        if feats is not None:
            batch["enc_embeds"] = feats[r]
        pool.append(batch)
    return pool
