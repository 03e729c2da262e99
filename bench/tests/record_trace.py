"""Record the small TPU trace that ``test_bench_trace.py`` reduces.

    python3 bench/tests/record_trace.py <out_dir>

On one chip: inside a ``bench.window`` span, three rounds of a jitted
matmul step (``bench.generate`` spans), the program's router kernel at a
64-row batch (``bench.score``) and a 0.2 s sleep (``bench.wait``). Copies
the ``.xplane.pb`` to ``<out_dir>/small_trace.xplane.pb``.
"""
import glob
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2

    @jax.jit
    def matmul_step(x, w):
        return jnp.tanh(x @ w)

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((1024, 1024), np.float32))
    w = jnp.asarray(rng.standard_normal((1024, 1024), np.float32))
    q = jnp.asarray(rng.standard_normal((64, 768), np.float32))
    wq = jnp.asarray(rng.standard_normal((768, 20), np.float32))
    memb = jnp.asarray(rng.standard_normal((2, 20), np.float32))
    wo = jnp.asarray(rng.standard_normal((20, 2), np.float32))
    wk = jnp.asarray(rng.standard_normal((20, 20), np.float32))
    bo = jnp.zeros((2,), jnp.float32)
    kt, vt = ops.pool_projections(wk, wk, memb)

    def score():
        return np.asarray(ops.router_xattn_pool(q, wq, kt, vt, wo, bo))

    np.asarray(matmul_step(x, w))
    score()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.generate"):
                np.asarray(matmul_step(x, w))
            with jax.profiler.TraceAnnotation("bench.score"):
                score()
        with jax.profiler.TraceAnnotation("bench.wait"):
            time.sleep(0.2)
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(found[0], os.path.join(out_dir, "small_trace.xplane.pb"))
    shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
