"""Record the small TPU trace that ``test_bench.py`` checks the trace
reduction against: two exchange kernels (quantize, dequant-accumulate) on a
short buffer and one jitted matmul, under ``bench.*`` host spans.

    python bench/tests/record_trace.py <out_dir>     # on a TPU chip
"""

import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.profiler import TraceAnnotation  # noqa: E402

from bench import trace  # noqa: E402
from repro.kernels.tdm_compress import tdm_compress as kern  # noqa: E402

N = 1 << 20


def main(out_dir: str) -> None:
    quant = jax.jit(lambda x: kern.quantize_fwd(x, block=1024))
    deq = jax.jit(lambda q, s, a: kern.dequant_accumulate_fwd(q, s, a, jnp.float32(0.5), block=1024))
    mat = jax.jit(lambda a: a @ a)
    x = jnp.linspace(-1.0, 1.0, N, dtype=jnp.float32)
    a = jnp.ones((1024, 1024), jnp.bfloat16)
    q, s = quant(x)
    jax.block_until_ready((deq(q, s, x), mat(a)))
    tdir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(tdir, profiler_options=opts)
    with TraceAnnotation("bench.window"):
        with TraceAnnotation("bench.dispatch"):
            q, s = quant(x)
            acc = deq(q, s, x)
        with TraceAnnotation("bench.drain"):
            jax.block_until_ready(acc)
        with TraceAnnotation("bench.dispatch"):
            b = mat(a)
        with TraceAnnotation("bench.drain"):
            jax.block_until_ready(b)
    jax.profiler.stop_trace()
    out = pathlib.Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(trace.find_xplane(tdir), out / "small.xplane.pb")
    shutil.rmtree(tdir)


if __name__ == "__main__":
    main(sys.argv[1])
