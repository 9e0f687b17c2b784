"""Readings that the limits of ``correct`` are set from, for one cell, in one
process on the chips the cell asks for.

    python bench/calibrate.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 7,8,9] [--faults half_batch,no_exchange] [--out f.json]

- program: for each of ``--seeds``, the cell's set-up (state from the seed,
  the first rounds through ``fl_train.run``) and the reference, compared as
  a run compares them: the lower readings.
- control: the reference computed in fp8 (e4m3, one scale per tensor, on
  every matmul operand forward and backward), put in the program's place
  and compared with the float32 reference: the upper readings.
- faults: the reference with one fault planted (``half_batch``: the loss
  over half the batch; ``no_exchange``: the exchange left out; ``frozen``:
  the state returned unchanged), compared with the clean reference.

Benchmark runs never run this. Each reading set is printed as one JSON line
and, with ``--out``, written there.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
FP8_MAX = 448.0


def fp8_cast():
    """Round to float8 e4m3 with one scale per tensor, forward and backward."""
    import jax
    import jax.numpy as jnp

    def q(a):
        s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / FP8_MAX
        return (a / s).astype(jnp.float8_e4m3fn).astype(a.dtype) * s

    @jax.custom_vjp
    def cast(a):
        return q(a)

    cast.defvjp(lambda a: (q(a), None), lambda _, g: (q(g),))
    return cast


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    from bench import harness, reference, traffic

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    conf, model = harness.load_config(cell["config"])
    mix = traffic.load(cell["traffic"])
    runner = harness.load_module(ROOT / "bench" / "runners" / f"{mix['runner']}.py", "drv")
    devices = jax.devices()
    steps = runner.FIRST_ROUNDS
    ints = lambda s: [int(x) for x in s.split(",") if x]
    out = []

    def emit(kind, seed, numbers, **extra):
        row = {"kind": kind, "seed": seed, **numbers, **extra,
               "t": round(time.perf_counter() - T0, 1)}
        out.append(row)
        print(json.dumps(row), flush=True)

    for seed in ints(args.seeds):
        c = runner.FLCell(model, conf, mix, seed)
        prog = c.setup()
        batches, key = c.warm_batches(), c.key
        c.free()
        del c
        ref = reference.run(model, conf, mix, batches, key, devices, steps)
        emit("program", seed, reference.compare(prog, ref),
             left_out=int((~reference.kept_leaves(ref)).sum()), loss=prog["loss"].tolist())
    for seed in dict.fromkeys(ints(args.control_seeds) + ints(args.fault_seeds)):
        batches = traffic.make_pool(mix, conf["vocab_size"], seed,
                                     runner.encoder_features(model, conf))[:steps]
        key = traffic.seed_words(seed)
        ref = reference.run(model, conf, mix, batches, key, devices, steps)
        if seed in ints(args.control_seeds):
            low = reference.run(model, conf, mix, batches, key, devices, steps,
                                cast=fp8_cast())
            emit("control_fp8", seed, reference.compare(low, ref))
        if seed in ints(args.fault_seeds):
            for fault in args.faults.split(","):
                bad = reference.run(model, conf, mix, batches, key, devices, steps,
                                    fault=fault)
                emit(f"fault_{fault}", seed, reference.compare(bad, ref))
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
