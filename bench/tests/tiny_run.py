"""One run of a cell's harness on the CPU at a tiny size (widths, depth,
vocabulary and sequence cut to a test's size), optionally with a fault
planted in the timed path. Prints the result object. The cell's limits
hold at its own size; at this size the numbers of sound runs are larger,
so a test passes limits of its own (``--limits``, JSON).

    python bench/tests/tiny_run.py --config <config> --traffic <traffic>
        [--fault frozen|half_batch|no_exchange] [--limits '{"loss_gap": ..., ...}']

For a four-node mix set ``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
"""

import argparse
import copy
import json
import pathlib
import sys
import time

T0 = time.perf_counter()
ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import harness, traffic  # noqa: E402

TINY = {
    "mamba2-780m": dict(d_model=64, n_layer=2, vocab_size=256),
    "whisper-base": dict(d_model=64, encoder_layers=1, decoder_layers=2,
                         encoder_attention_heads=4, decoder_attention_heads=4,
                         encoder_ffn_dim=96, decoder_ffn_dim=96, vocab_size=128,
                         max_source_positions=32),
}


def tiny_config(name):
    conf, model = harness.load_config(name)
    conf = copy.deepcopy(conf)
    conf.update(TINY[name])
    if "mamba2_layer" in conf:
        conf["mamba2_layer"].update(d_state=16, headdim=16, chunk_size=16)
    return conf, model


def tiny_mix(cell):
    mix = traffic.load(cell["traffic"])
    return dict(mix, seq=16 if mix["batch"] > 8 else 32, batch=min(mix["batch"], 32),
                pool_rounds=4, trace_seconds=1)


def plant(fault: str):
    """A hook that breaks the timed path of the cell it is given."""

    def hook(c):
        rounds = c.cache.cache

        class Broken:
            n_nodes = rounds.n_nodes

            def expected_collectives(self, rel, state):
                return rounds.expected_collectives(rel, state)

            def __call__(self, rel, example_args=None):
                fn = rounds(rel)

                def frozen(state, batch):
                    _, losses = fn(jax.tree.map(jnp.copy, state), batch)
                    return state, losses

                def half_batch(state, batch):
                    half = {k: v[:, :, : v.shape[2] // 2] for k, v in batch.items()}
                    return fn(state, half)

                return {"frozen": frozen, "half_batch": half_batch}[fault]

        if fault == "no_exchange":
            from repro.core import fused

            fused.fused_tdm_fla_round = lambda params, rel, *a, **k: (params, None)
        else:
            c.cache.cache = Broken()

    return hook if fault else None


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--fault", default="")
    ap.add_argument("--seed", type=int, default=2**31 + 7)
    ap.add_argument("--limits", default="")
    args = ap.parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {"name": f"{args.config}.{args.traffic}", "config": args.config,
            "traffic": args.traffic}
    conf, model = tiny_config(args.config)
    res = harness.run_cell(
        bench, cell, args.seed, 1.0, False, jax.devices(), T0,
        log=lambda m: print(m, file=sys.stderr), conf=conf, model=model,
        mix=tiny_mix(cell), limits=json.loads(args.limits) if args.limits else None,
        program_fault=plant(args.fault),
    )
    print(json.dumps(res))


if __name__ == "__main__":
    main()
