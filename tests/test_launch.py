"""Launch layer: abstract case construction (no allocation), shape specs,
skip rules, roofline math. The actual 512-device lower/compile runs live
in repro.launch.dryrun (results under benchmarks/results/dryrun)."""

from pathlib import Path

import jax
import jax.numpy as jnp

import pytest

from repro.configs import get_config
from repro.launch.compile_cache import use_compile_cache
from repro.launch.roofline import V5E, derive_roofline, model_flops
from repro.launch.hlo_analysis import HloCost
from repro.launch.specs import SHAPES, build_case, skip_reason


class TestShapes:
    def test_assigned_shapes_exact(self):
        assert (SHAPES["train_4k"].seq, SHAPES["train_4k"].batch) == (4096, 256)
        assert (SHAPES["prefill_32k"].seq, SHAPES["prefill_32k"].batch) == (32768, 32)
        assert (SHAPES["decode_32k"].seq, SHAPES["decode_32k"].batch) == (32768, 128)
        assert (SHAPES["long_500k"].seq, SHAPES["long_500k"].batch) == (524288, 1)

    def test_single_documented_skip(self):
        skips = [
            (a, s)
            for a in ("seamless-m4t-large-v2", "glm4-9b", "zamba2-7b")
            for s in SHAPES.values()
            if skip_reason(get_config(a), s)
        ]
        assert skips == [("seamless-m4t-large-v2", SHAPES["long_500k"])]


class TestAbstractCases:
    """build_case produces ShapeDtypeStructs only — zero device allocation."""

    def _assert_abstract(self, tree):
        for leaf in jax.tree.leaves(tree):
            assert isinstance(leaf, jax.ShapeDtypeStruct), type(leaf)

    @pytest.mark.parametrize(
        "arch,shape",
        [
            ("glm4-9b", "train_4k"),
            ("mixtral-8x22b", "prefill_32k"),
            ("zamba2-7b", "decode_32k"),
            ("xlstm-1.3b", "long_500k"),
            ("seamless-m4t-large-v2", "decode_32k"),
            ("qwen2-vl-72b", "prefill_32k"),
        ],
    )
    def test_full_size_cases_abstract(self, arch, shape):
        case = build_case(arch, shape)
        self._assert_abstract(case.args)
        assert callable(case.step)

    def test_long500k_dense_gets_window(self):
        case = build_case("glm4-9b", "long_500k")
        # ring cache bounded by the serving window, not 524288
        assert case.args[1]["k"].shape[2] == 8192

    def test_long500k_mixtral_native_swa(self):
        case = build_case("mixtral-8x22b", "long_500k")
        assert case.args[1]["k"].shape[2] == 4096

    def test_long500k_ssm_state_only(self):
        case = build_case("xlstm-1.3b", "long_500k")
        assert "k" not in case.args[1]  # no KV cache at all

    def test_train_batch_shapes(self):
        case = build_case("glm4-9b", "train_4k")
        assert case.args[2]["tokens"].shape == (256, 4096)
        assert case.donate == (0, 1)


class TestRoofline:
    def test_terms_and_dominance(self):
        cost = HloCost(flops=197e12, dot_bytes=819e9 * 2)
        cost.collective_bytes["all-reduce"] = 50e9 * 3
        cfg = get_config("glm4-9b")
        r = derive_roofline(cost, cfg, SHAPES["train_4k"], chips=256)
        assert r.compute_s == pytest.approx(1.0)
        assert r.memory_s == pytest.approx(2.0)
        assert r.collective_s == pytest.approx(3.0)
        assert r.dominant == "collective"
        assert r.step_s == pytest.approx(6.0)

    def test_model_flops_conventions(self):
        dense = get_config("glm4-9b")
        moe = get_config("mixtral-8x22b")
        t = SHAPES["train_4k"]
        d = SHAPES["decode_32k"]
        assert model_flops(dense, t) == pytest.approx(
            6 * dense.param_count() * 256 * 4096
        )
        # MoE uses ACTIVE params
        assert model_flops(moe, t) == pytest.approx(
            6 * moe.active_param_count() * 256 * 4096
        )
        assert model_flops(dense, d) == pytest.approx(
            2 * dense.param_count() * 128
        )


class TestDecodeRulesV3:
    def test_embed_sharded_over_data(self):
        from repro import sharding as sh

        mesh = jax.sharding.AbstractMesh((16, 16), ("data", "model"))
        ctx = sh._Ctx(mesh, sh.DECODE_RULES_V3)
        assert sh._resolve_dim(8192, "embed", ctx, set()) == "data"
        # batch stays replicated in V2/V3
        assert sh._resolve_dim(128, "batch", ctx, set()) is None


class TestCompileCache:
    def test_env_dir_is_left_alone(self, monkeypatch, tmp_path):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        before = jax.config.jax_compilation_cache_dir
        assert use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_fixed_in_the_checkout(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        before = jax.config.jax_compilation_cache_dir
        want = str(Path(__file__).resolve().parents[1] / ".jax_cache")
        try:
            assert use_compile_cache() == want
            assert jax.config.jax_compilation_cache_dir == want
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
