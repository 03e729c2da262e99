"""Launch layer: sharding rules, input specs, HLO collective parsing.

These tests run on 1 CPU device: sharding *rules* are exercised against an
AbstractMesh with the production 16x16 shape (no real devices needed), and a
real (1,1) mesh covers the end-to-end jit path. The persistent compilation
cache helper's placement is checked last.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.configs.shapes import SHAPES, input_specs, shape_applicable
from repro.launch.hlo_analysis import (
    collective_bytes_per_device, parse_collectives, _shape_bytes,
)
from repro.launch.sharding import (
    batch_axes, cache_shardings, param_spec, param_shardings, train_rules,
    decode_rules,
)
from repro.models import lm as lm_mod


def abstract_mesh(multi_pod=False):
    if multi_pod:
        return AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    return AbstractMesh((16, 16), ("data", "model"))


class TestParamSpecs:
    def test_embedding_sharded_on_vocab(self):
        mesh = abstract_mesh()
        cfg = get_config("gemma3-27b")
        assert param_spec(cfg, mesh, "embedding/table", 2) == P("model", "data")
        assert param_spec(cfg, mesh, "embedding/head", 2) == P("data", "model")

    def test_attention_tp(self):
        mesh = abstract_mesh()
        cfg = get_config("granite-3-8b")
        # stacked pattern params have a leading repeat axis
        assert param_spec(cfg, mesh, "pattern/0/mixer/wq", 3) == P(None, "data", "model")
        assert param_spec(cfg, mesh, "pattern/0/mixer/wo", 3) == P(None, "model", "data")
        assert param_spec(cfg, mesh, "pattern/0/norm1/scale", 2) == P(None, None)

    def test_moe_expert_parallel(self):
        mesh = abstract_mesh()
        cfg = get_config("llama4-maverick-400b-a17b")
        # pattern position 1 is the MoE layer
        assert param_spec(cfg, mesh, "pattern/1/ffn/w_gate", 4) == P(
            None, "model", "data", None)
        assert param_spec(cfg, mesh, "pattern/1/ffn/w_down", 4) == P(
            None, "model", None, "data")
        # shared expert = plain MLP sharding
        assert param_spec(cfg, mesh, "pattern/1/ffn/shared/w_gate", 3) == P(
            None, "data", "model")

    def test_multipod_folds_pod_into_fsdp(self):
        mesh = abstract_mesh(multi_pod=True)
        cfg = get_config("granite-3-8b")
        spec = param_spec(cfg, mesh, "pattern/0/mixer/wq", 3)
        assert spec == P(None, ("pod", "data"), "model")

    def test_every_param_of_every_arch_divides(self):
        """All param shardings must divide their dims on the 16x16 mesh
        (jit argument shardings require exact divisibility)."""
        mesh = abstract_mesh()
        from repro.common.tree import flatten_with_paths
        for name in ARCH_IDS:
            cfg = get_config(name)
            abstract = lm_mod.abstract_params(cfg, dtype=jnp.bfloat16)
            for path, leaf in flatten_with_paths(abstract).items():
                spec = param_spec(cfg, mesh, path, len(leaf.shape))
                for dim, ax in zip(leaf.shape, spec):
                    if ax is None:
                        continue
                    size = np.prod([mesh.shape[a] for a in
                                    (ax if isinstance(ax, tuple) else (ax,))])
                    assert dim % size == 0, (name, path, leaf.shape, spec)


class TestCacheSpecs:
    def test_all_arch_decode_caches_divide(self):
        mesh = abstract_mesh()
        for name in ARCH_IDS:
            cfg = get_config(name)
            for shape_name in ("decode_32k", "long_500k"):
                shape = SHAPES[shape_name]
                if not shape_applicable(cfg, shape):
                    continue
                caches = lm_mod.abstract_caches(cfg, shape.global_batch,
                                                shape.seq_len)
                shardings = cache_shardings(cfg, mesh, caches)
                for leaf, sh in zip(jax.tree.leaves(caches),
                                    jax.tree.leaves(shardings)):
                    for dim, ax in zip(leaf.shape, sh.spec):
                        if ax is None:
                            continue
                        size = np.prod([mesh.shape[a] for a in
                                        (ax if isinstance(ax, tuple) else (ax,))])
                        assert dim % size == 0, (name, shape_name, leaf.shape,
                                                 sh.spec)

    def test_long_context_shards_seq_over_all_axes(self):
        mesh = abstract_mesh()
        cfg = get_config("gemma3-27b")
        shape = SHAPES["long_500k"]
        caches = lm_mod.abstract_caches(cfg, 1, shape.seq_len)
        shardings = cache_shardings(cfg, mesh, caches)
        # global layers (pattern pos 5) hold the full 500k cache
        k_spec = jax.tree.leaves(
            shardings["pattern"][5], is_leaf=lambda x: hasattr(x, "spec")
        )
        specs = [s.spec for s in jax.tree.leaves(shardings["pattern"][5])]
        assert any(("data", "model") in (ax if isinstance(ax, tuple) else (ax,))
                   or ax == ("data", "model")
                   for sp in specs for ax in sp if ax is not None)


class TestRules:
    def test_train_vs_decode_cache_axis(self):
        mesh = abstract_mesh()
        assert train_rules(mesh)["cache_seq"] is None
        assert decode_rules(mesh)["cache_seq"] == "model"

    def test_batch_axes_multipod(self):
        assert batch_axes(abstract_mesh(True)) == ("pod", "data")
        assert batch_axes(abstract_mesh(False)) == "data"


class TestInputSpecs:
    def test_train_specs(self):
        cfg = get_config("qwen3-0.6b")
        specs = input_specs(cfg, SHAPES["train_4k"])
        assert specs["tokens"].shape == (256, 4096)
        assert specs["labels"].dtype == jnp.int32

    def test_decode_specs_have_one_token(self):
        cfg = get_config("qwen3-0.6b")
        specs = input_specs(cfg, SHAPES["decode_32k"])
        assert specs["token"].shape == (128, 1)
        assert specs["pos"].shape == ()
        assert "caches" in specs

    def test_vlm_specs_include_media(self):
        cfg = get_config("llama-3.2-vision-90b")
        specs = input_specs(cfg, SHAPES["train_4k"])
        assert specs["media"].shape == (256, 1601, 1280)

    def test_long500k_gate(self):
        assert not shape_applicable(get_config("qwen3-0.6b"), SHAPES["long_500k"])
        assert shape_applicable(get_config("xlstm-1.3b"), SHAPES["long_500k"])
        assert shape_applicable(get_config("gemma3-27b"), SHAPES["long_500k"])
        assert shape_applicable(get_config("jamba-1.5-large-398b"),
                                SHAPES["long_500k"])


class TestHLOParsing:
    def test_shape_bytes(self):
        assert _shape_bytes("f32[128,256]") == 128 * 256 * 4
        assert _shape_bytes("bf16[16]") == 32
        assert _shape_bytes("(f32[8], s32[4])") == 8 * 4 + 4 * 4

    def test_parse_collectives(self):
        hlo = """
  %ag = f32[32,128]{1,0} all-gather(%x), replica_groups={{0,1,2,3}}, dimensions={0}
  %ar = bf16[64]{0} all-reduce(%y), replica_groups=[4,8]<=[32], to_apply=%sum
  %cp = f32[16]{0} collective-permute(%z), source_target_pairs={{0,1}}
"""
        colls = parse_collectives(hlo)
        kinds = [c["kind"] for c in colls]
        assert kinds == ["all-gather", "all-reduce", "collective-permute"]
        assert colls[0]["group"] == 4
        assert colls[1]["group"] == 8
        total, by_kind = collective_bytes_per_device(colls)
        expect_ag = 32 * 128 * 4 * 3 / 4
        expect_ar = 2 * 64 * 2 * 7 / 8
        expect_cp = 16 * 4
        assert np.isclose(total, expect_ag + expect_ar + expect_cp)

    def test_no_collectives_on_single_device(self):
        f = jax.jit(lambda x: x @ x)
        compiled = f.lower(jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
        colls = parse_collectives(compiled.as_text())
        total, _ = collective_bytes_per_device(colls)
        assert total == 0.0


class TestSmallMeshEndToEnd:
    def test_train_step_jits_on_1x1_mesh(self):
        from repro.launch.mesh import make_debug_mesh
        from repro.launch.steps import make_train_step, abstract_opt_state
        from repro.training.optim import adam_init

        mesh = make_debug_mesh(1, 1)
        cfg = get_smoke_config("qwen3-0.6b")
        params = lm_mod.init_lm(jax.random.key(0), cfg)
        from repro.launch.steps import TRAIN_ADAM
        opt = adam_init(TRAIN_ADAM, params)
        step = jax.jit(make_train_step(cfg, mesh))
        batch = {
            "tokens": jnp.zeros((4, 32), jnp.int32),
            "labels": jnp.zeros((4, 32), jnp.int32),
        }
        with mesh:
            loss, params, opt = step(params, opt, batch)
        assert np.isfinite(float(loss))


class TestCompileCache:
    ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def test_env_var_is_left_to_jax(self, monkeypatch):
        from repro.common.compile_cache import ENV_VAR, enable_compile_cache

        monkeypatch.setenv(ENV_VAR, "/elsewhere/cache")
        before = jax.config.jax_compilation_cache_dir
        assert enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_fixed_dir_in_checkout(self, monkeypatch):
        from repro.common.compile_cache import ENV_VAR, enable_compile_cache

        monkeypatch.delenv(ENV_VAR, raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            path = enable_compile_cache()
            assert path == os.path.join(self.ROOT, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
        finally:
            jax.config.update("jax_compilation_cache_dir", before)

    @pytest.mark.parametrize("env_set", [True, False],
                             ids=["env-dir", "checkout-dir"])
    def test_compiles_land_in_one_place(self, tmp_path, env_set):
        """A process writes its compiles to the one cache directory, and a
        second process of the same checkout reads them back. The checkout
        is a copy whose ``src`` links to this one, so the default
        ``.jax_cache/`` lands in ``tmp_path``."""
        import subprocess
        import sys

        checkout = tmp_path / "checkout"
        checkout.mkdir()
        os.symlink(os.path.join(self.ROOT, "src"), checkout / "src")
        cache = (tmp_path / "env-cache" if env_set
                 else checkout / ".jax_cache")
        script = (
            "import jax, jax.numpy as jnp\n"
            "from jax import monitoring\n"
            "from repro.common.compile_cache import enable_compile_cache\n"
            "hits = []\n"
            "monitoring.register_event_listener(lambda e, **_: hits.append(e)"
            " if e == '/jax/compilation_cache/cache_hits' else None)\n"
            "enable_compile_cache()\n"
            "jax.config.update('jax_persistent_cache_min_compile_time_secs',"
            " 0)\n"
            "jax.jit(lambda x: x @ x + 1)(jnp.ones((64, 64)))"
            ".block_until_ready()\n"
            "print(len(hits))\n")
        env = {k: v for k, v in os.environ.items()
               if k not in ("JAX_COMPILATION_CACHE_DIR", "PYTHONPATH")}
        env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(checkout / "src"))
        if env_set:
            env["JAX_COMPILATION_CACHE_DIR"] = str(cache)
        hits = [subprocess.run([sys.executable, "-c", script], env=env,
                               check=True, capture_output=True, text=True,
                               timeout=120, cwd=tmp_path).stdout.split()[-1]
                for _ in range(2)]
        assert any(n.startswith("jit__lambda") for n in os.listdir(cache))
        assert os.path.isdir(checkout / ".jax_cache") is not env_set
        assert hits[0] == "0" and int(hits[1]) >= 1
