"""Run the routed serving path once on a TPU chip, at published widths.

    python chip_smoke.py               # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4     # four chips: (a) and the fleet (e)

Phases, each printing one line that starts with its letter:

  (a) device   platform, kind and count as JAX reports them; anything but
               a TPU fails here, before any work.
  (b) kernels  ``router_xattn_pool`` and ``pairwise_l2`` compiled at the
               serving widths, checked for the Mosaic kernel in the
               compiled program and against ``kernels/ref.py``.
  (c) serve    ``repro.launch.serve.main`` in this process: qwen3-0.6b and
               granite-moe-1b-a400m at their published widths, a trained
               router, the semantic cache, and Poisson traffic.
  (d) cache    qwen3-0.6b prefill plus decode steps through the KV cache
               against one full forward over the same tokens.
  (e) fleet    (``--chips 4`` only) the in-process plane with four
               workers, each pool member on its owner's chip, against the
               same seeded run with every member on chip 0.

Weights, router training data and traffic all derive from ``--seed``;
nothing is read from disk. Any failed check exits non-zero. The last line
of a passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.common import profile_slot  # noqa: E402
from repro.common.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs import get_config, get_smoke_config  # noqa: E402
from repro.core.model_repr import N_CLUSTERS  # noqa: E402
from repro.core.predictors import PREDICTORS  # noqa: E402
from repro.data.featurizer import EMB_DIM  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.kernels import ref as kref  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.models import lm as lm_mod  # noqa: E402
from repro.obs.profiling import LayerProfiler  # noqa: E402

POOL = ("qwen3-0.6b", "granite-moe-1b-a400m")
REQUESTS = 16
MAX_NEW = 8
EPOCHS = 3
SCORE_BATCH = 64             # engine pads score batches to multiples of 64
# pairwise_l2 shapes: the cache probe (one score batch against a full
# 256-entry cache) and the radius calibration (512 corpus rows).
L2_SHAPES = ((SCORE_BATCH, 256), (512, 512))

# Kernel tolerance, as max |kernel - ref| over the reference's scale. Both
# contract in full float32 ("highest"), so they differ by rounding order:
# 7.3e-7 (router_xattn) and 2.5e-7 (pairwise_l2) on a v5e. Contracting in
# bf16 instead, the TPU's default, measured 3.8e-2 (router_xattn kernel),
# 5.1e-3 (its pool projections alone) and 3.0e-4 (pairwise_l2) there and
# fails this; a padding or masking fault is an O(1) error.
KERNEL_TOL = 1e-4
# Cache agreement tolerance, as max |cached - full| over max |full logit|.
# Both paths run in float32 at "highest" precision, so they differ only by
# summation order (the cached attention reads keys the prefill wrote; the
# full forward recomputes them): about 1e-6 relative on the CPU, flat from
# 2 to 28 layers at the smoke widths, and 4.2e-7 on a v5e at published
# widths. The bound leaves room for the TPU's own f32 arithmetic; a cache
# at the wrong position or a missed update moves logits by O(1) of their
# scale.
CACHE_TOL = 1e-3
CACHE_BATCH, CACHE_PROMPT, CACHE_STEPS = 2, 24, 3


def fail(msg: str):
    raise SystemExit(f"chip_smoke FAILED: {msg}")


def check(cond, msg: str) -> None:
    if not cond:
        fail(msg)


def profiled(call):
    """Run ``call()`` with a fresh layer profiler installed: (its result,
    the profiler, which holds the XLA compiles and persistent-cache hits
    of the call, charged to the program's spans)."""
    prof = LayerProfiler()
    profile_slot.install(prof)
    try:
        return call(), prof
    finally:
        profile_slot.install(None)


def compile_split(prof) -> dict:
    """Compiles charged to each span, most first."""
    return dict(sorted(((k, v) for k, v in prof.compiles.items() if v),
                       key=lambda kv: -kv[1]))


def unit_rows(key, n: int, d: int):
    x = jax.random.normal(key, (n, d), jnp.float32)
    return x / jnp.linalg.norm(x, axis=1, keepdims=True)


def rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1e-30))


def phase_device(platform: str, chips: int) -> dict:
    devs = jax.devices()
    d = devs[0]
    print(f"(a) device: platform {d.platform}  kind {d.device_kind}  "
          f"count {len(devs)}", flush=True)
    check(d.platform == platform,
          f"JAX found platform {d.platform!r}, not {platform!r}")
    check(len(devs) >= chips, f"{len(devs)} devices, {chips} asked for")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def compiled_kernel(fn, platform, *args):
    """Compile ``fn`` for these arguments; on TPU the program must hold
    the Mosaic kernel, not an XLA fallback."""
    compiled = jax.jit(fn).lower(*args).compile()
    if platform == "tpu":
        check("tpu_custom_call" in compiled.as_text(),
              f"{fn.__name__}: no tpu_custom_call in the compiled program")
    return compiled


def phase_kernels(platform: str, seed: int) -> None:
    ks = jax.random.split(jax.random.key(seed), 4)
    k = len(POOL)
    p = PREDICTORS["attn"].init(ks[0], EMB_DIM, k, N_CLUSTERS)
    m_emb = jax.random.normal(ks[1], (k, N_CLUSTERS), jnp.float32)
    kt, vt = kops.pool_projections(p["wk"], p["wv"], m_emb)
    q = unit_rows(ks[2], SCORE_BATCH, EMB_DIM)
    args = (q, p["wq"], kt, vt, p["wo"], p["bo"])
    out = compiled_kernel(kops.router_xattn_pool, platform, *args)(*args)
    with jax.default_matmul_precision("highest"):
        want = kref.router_xattn_ref(q, p["wq"], p["wk"], p["wv"], p["wo"],
                                     p["bo"], m_emb)
    errs = {f"router_xattn {SCORE_BATCH}x{EMB_DIM}->K{k}": rel_err(out, want)}
    for n, c in L2_SHAPES:
        kx, kc = jax.random.split(jax.random.fold_in(ks[3], n))
        x, cc = unit_rows(kx, n, EMB_DIM), unit_rows(kc, c, EMB_DIM)
        out = compiled_kernel(kops.pairwise_l2, platform, x, cc)(x, cc)
        with jax.default_matmul_precision("highest"):
            want = kref.pairwise_l2_ref(x, cc)
        errs[f"pairwise_l2 {n}x{c}x{EMB_DIM}"] = rel_err(out, want)
    custom = "tpu_custom_call" if platform == "tpu" else "interpret mode"
    print(f"(b) kernels: {custom}; max |kernel - ref| / scale "
          + "  ".join(f"{name} {e:.3e}" for name, e in errs.items())
          + f"  (tolerance {KERNEL_TOL:g})", flush=True)
    for name, e in errs.items():
        check(e <= KERNEL_TOL, f"{name} off the reference by {e:.3e}")


def serve_argv(seed: int, full_width: bool) -> list:
    # Willingness-to-pay just above where the cheaper member wins, under
    # a $/window budget: the governor tightens lambda after the first
    # batches, so both members serve.
    argv = ["--pool", ",".join(POOL), "--trace", "poisson",
            "--requests", str(REQUESTS), "--max-new", str(MAX_NEW),
            "--epochs", str(EPOCHS), "--semcache", "--seed", str(seed),
            "--lam", "5e-5", "--budget", "4e-5", "--budget-window", "0.02"]
    return argv + (["--full-width"] if full_width else [])


def check_served(summary: dict, label: str) -> None:
    """Every request completed with ``MAX_NEW`` tokens, and every
    completion is accounted to a member or to a cache hit."""
    done = summary["completed"]
    counts = summary["per_member_counts"]
    check(done == REQUESTS, f"{label}: {done}/{REQUESTS} requests completed")
    lens = [None if o is None else len(o) for o in summary["outputs"]]
    check(all(n == MAX_NEW for n in lens),
          f"{label}: output lengths {lens}, {MAX_NEW} expected")
    check(sum(counts.values()) + summary.get("cache_hits", 0) == done,
          f"{label}: member counts {counts} plus cache hits "
          f"{summary.get('cache_hits', 0)} != {done} completed")
    check(all(counts[name] >= 1 for name in POOL),
          f"{label}: not every member served: {counts}")


def phase_serve(platform: str, seed: int, full_width: bool) -> None:
    summary, prof = profiled(lambda: serve.main(serve_argv(seed,
                                                           full_width)))
    check_served(summary, "serve")
    # Radius calibration runs pairwise_l2; on TPU the engine scores
    # through the kernel with no flag asking for it.
    expect = ["repro.kernels.pairwise_l2"] + (
        ["repro.kernels.router_xattn_pool"] if platform == "tpu" else [])
    kernels = {k: v for k, v in sorted(prof.calls.items())
               if k.startswith("repro.kernels.")}
    check(all(kernels.get(name, 0) >= 1 for name in expect),
          f"serve path dispatched kernels {kernels}, expected {expect}")
    stats = prof.totals()
    peak = summary["peak_bytes_in_use"]
    check(platform != "tpu" or peak, "no peak device memory reported")
    print(f"(c) serve: {'  '.join(summary['pool'])}; completed "
          f"{summary['completed']}/"
          f"{REQUESTS} with {MAX_NEW} tokens each; per-member "
          f"{summary['per_member_counts']}; cache hits "
          f"{summary.get('cache_hits', 0)}; kernel calls {kernels}; "
          f"{stats['compiles']} XLA compile requests ({stats['cache_hits']} "
          f"served from the persistent cache; by span "
          f"{compile_split(prof)}); "
          f"peak device memory {peak} bytes", flush=True)


def phase_cache(seed: int, full_width: bool) -> None:
    cfg = (get_config if full_width else get_smoke_config)(POOL[0])
    params = lm_mod.init_lm(jax.random.key(seed), cfg)
    b, s, steps = CACHE_BATCH, CACHE_PROMPT, CACHE_STEPS
    tokens = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (b, s + steps)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        caches = lm_mod.init_caches(cfg, b, s + steps)
        logits, caches = lm_mod.apply_lm_prefill(cfg, params, tokens[:, :s],
                                                 caches)
        got = [logits[:, -1]]
        for i in range(steps):
            logits, caches = lm_mod.apply_lm_decode(
                cfg, params, tokens[:, s + i:s + i + 1], caches,
                jnp.int32(s + i))
            got.append(logits[:, -1])
        full, _ = lm_mod.apply_lm_train(cfg, params, tokens, remat=False)
    v = cfg.vocab_size
    got = jnp.stack(got, axis=1)[..., :v]
    want = full[:, s - 1:, :v]
    err = rel_err(got, want)
    print(f"(d) cache: {cfg.name} d_model {cfg.d_model} x {cfg.n_layers} "
          f"layers, prefill {s} + {steps} decode steps vs full forward: "
          f"max |cached - full| / max |full| {err:.3e} (tolerance "
          f"{CACHE_TOL:g}, max |full| {float(jnp.max(jnp.abs(want))):.4g})",
          flush=True)
    check(np.all(np.isfinite(np.asarray(got))), "non-finite cached logits")
    check(err <= CACHE_TOL, f"cached logits off the full forward by {err:.3e}")


def phase_fleet(seed: int, full_width: bool, n_workers: int) -> None:
    argv = serve_argv(seed, full_width) + [
        "--workers", str(n_workers), "--transport", "local", "--online",
        "--online-update-every", "4"]
    runs = {}
    for label, devices in (("spread", None), ("chip0", jax.devices()[:1])):
        runs[label], prof = profiled(
            lambda: serve.main(argv, devices=devices))
        check_served(runs[label], label)
        runs[label]["stats"] = prof.totals()
    spread, one = runs["spread"], runs["chip0"]
    check(len(set(spread["pool_device"].values())) == len(POOL),
          f"members share devices: {spread['pool_device']}")
    check(set(one["pool_device"].values()) == {jax.devices()[0].id},
          f"baseline not on chip 0: {one['pool_device']}")
    for key in ("per_member_counts", "router_versions", "outputs"):
        check(spread[key] == one[key],
              f"{key} differ: spread {spread[key]} vs chip 0 {one[key]}")
    print(f"(e) fleet: {n_workers} workers, members on devices "
          f"{spread['pool_device']} vs all on {one['pool_device']}; "
          f"identical per-member {spread['per_member_counts']}, router "
          f"versions {spread['router_versions']} and all "
          f"{REQUESTS}x{MAX_NEW} tokens; compile requests "
          f"{spread['stats']['compiles']} + {one['stats']['compiles']}; "
          f"peak device memory {spread['peak_bytes_in_use']} then "
          f"{one['peak_bytes_in_use']} bytes", flush=True)


def run(chips: int = 1, seed: int = 0, *, platform: str = "tpu",
        full_width: bool = True) -> dict:
    """All phases for ``chips``; returns the device record. The CPU tests
    call this with ``platform="cpu"`` and ``full_width=False``."""
    device = phase_device(platform, chips)
    if chips > 1:
        phase_fleet(seed, full_width, chips)
    else:
        phase_kernels(platform, seed)
        phase_serve(platform, seed, full_width)
        phase_cache(seed, full_width)
    return device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the fleet phase across four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()
    device = run(args.chips, args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
