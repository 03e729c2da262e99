"""Record the small TPU trace with program spans that
``test_program_spans.py`` reads.

    python3 bench/tests/record_program_trace.py <out_dir>

On one chip, with the program's layer profiler installed, inside a
``bench.window`` span: two generate calls of a two-layer qwen3-0.6b at
its smoke widths through ``RoutedEngine.generate_member`` (prefill and
decode spans, the first call compiling), a scoring pass on the router
kernel (``repro.engine.score`` around ``repro.kernels.router_xattn_pool``)
and a 0.2 s sleep (``bench.wait``). Copies the ``.xplane.pb`` to
``<out_dir>/program_trace.xplane.pb``.
"""
import dataclasses
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
    import numpy as np

    from repro.common import profile_slot
    from repro.configs import get_smoke_config
    from repro.core.model_repr import N_CLUSTERS
    from repro.core.predictors import PREDICTORS
    from repro.core.router import PredictiveRouter
    from repro.data.featurizer import EMB_DIM
    from repro.models import lm as lm_mod
    from repro.obs.profiling import LayerProfiler
    from repro.serving import PoolMember, RoutedEngine

    if jax.devices()[0].platform != "tpu":
        print("record_program_trace: needs a TPU", file=sys.stderr)
        return 2
    cfg = dataclasses.replace(get_smoke_config("qwen3-0.6b"), n_layers=2)
    member = PoolMember(name=cfg.name, cfg=cfg,
                        params=lm_mod.init_lm(jax.random.key(0), cfg),
                        quality_profile=None, cost_rate=1e-4)
    key = jax.random.key(1)
    qp = PREDICTORS["attn"].init(key, EMB_DIM, 2, N_CLUSTERS)
    cp = PREDICTORS["attn"].init(jax.random.fold_in(key, 1), EMB_DIM, 2,
                                 N_CLUSTERS)
    router = PredictiveRouter("attn", "attn", qp, cp,
                              np.ones((2, N_CLUSTERS), np.float32))
    engine = RoutedEngine(router=router, pool=[member, member])
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 64, n).astype(np.int32) for n in (9, 14)]
    q = rng.standard_normal((8, EMB_DIM)).astype(np.float32)
    engine.score_emb(q)          # compile the scoring path first

    prof = LayerProfiler()
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    profile_slot.install(prof)
    jax.profiler.start_trace(tmp, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(2):
                engine.generate_member(0, prompts, max_new=3)
            engine.score_emb(q)
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(0.2)
    finally:
        jax.profiler.stop_trace()
        profile_slot.install(None)
    print(prof.report())
    found = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(found[0], os.path.join(out_dir, "program_trace.xplane.pb"))
    shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
