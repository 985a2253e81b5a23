"""Sharding strategies (fitter properties, rule coverage) and roofline
extraction (collective parsing incl. loop trip counts, model FLOPs)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get_config, get_smoke, list_archs
from repro.launch import roofline as rl
from repro.launch import sharding as sh
from repro.models import zoo


def host_mesh():
    from repro.launch.mesh import make_host_mesh

    return make_host_mesh()


class FakeMesh:
    """Duck-typed mesh with prescribed axis sizes for fitter tests."""

    def __init__(self, **shape):
        self.shape = shape
        self.axis_names = tuple(shape)


class TestFitSpec:
    M = FakeMesh(data=16, model=16, pod=2)

    def test_divisible_kept(self):
        assert sh.fit_spec((64, 32), P("data", "model"), self.M) == P("data", "model")

    def test_indivisible_dropped(self):
        assert sh.fit_spec((25, 32), P("data", "model"), self.M) == P(None, "model")

    def test_tuple_prefix_degradation(self):
        # 32 % (16*16) != 0 but 32 % 16 == 0 -> keep prefix ("data",)
        assert sh.fit_spec((32,), P(("data", "model")), self.M) == P("data")

    def test_no_duplicate_axis_use(self):
        got = sh.fit_spec((64, 64), P("model", "model"), self.M)
        assert got == P("model")  # second use dropped, trailing None trimmed

    def test_trailing_nones_trimmed(self):
        assert sh.fit_spec((64, 3, 3), P("data", None, None), self.M) == P("data")

    @given(
        dims=st.lists(st.integers(1, 512), min_size=1, max_size=4),
    )
    @settings(max_examples=50, deadline=None)
    def test_always_valid(self, dims):
        spec = P(*(["data", "model", ("data", "model"), None] * 1)[: len(dims)])
        fitted = sh.fit_spec(tuple(dims), spec, self.M)
        used = set()
        for dim, ax in zip(dims, tuple(fitted) + (None,) * (len(dims) - len(fitted))):
            if ax is None:
                continue
            assert dim % sh._axis_size(self.M, ax) == 0
            axes = ax if isinstance(ax, tuple) else (ax,)
            assert not (set(axes) & used)
            used.update(axes)


class TestStrategies:
    @pytest.mark.parametrize("name", sh.STRATEGIES)
    def test_make_strategy(self, name):
        S = sh.make_strategy(name, host_mesh())
        assert S.name == name and isinstance(S.batch, tuple)

    def test_unknown_strategy(self):
        with pytest.raises(ValueError):
            sh.make_strategy("bogus", host_mesh())

    def test_defaults_by_kind(self):
        dense, moe = get_config("qwen3-4b"), get_config("mixtral-8x7b")
        assert sh.default_strategy_name(dense, SHAPES["train_4k"]) == "fsdp"
        assert sh.default_strategy_name(dense, SHAPES["decode_32k"]) == "tp_sp"
        assert sh.default_strategy_name(moe, SHAPES["train_4k"]) == "ep"
        assert sh.default_strategy_name(moe, SHAPES["prefill_32k"]) == "ep_tp"

    @pytest.mark.parametrize("arch", list_archs())
    @pytest.mark.parametrize("strategy", sh.STRATEGIES)
    def test_param_shardings_build_for_all_archs(self, arch, strategy):
        """The fitter must produce a legal sharding for every arch x strategy
        (this is what 'every cell lowers' rests on)."""
        cfg = get_smoke(arch)
        mesh = host_mesh()
        S = sh.make_strategy(strategy, mesh)
        abstract = zoo.abstract_params(cfg)
        shards = sh.param_shardings(cfg, abstract, mesh, S)
        for leaf, shard in zip(jax.tree_util.tree_leaves(abstract), jax.tree_util.tree_leaves(shards)):
            assert isinstance(shard, NamedSharding)

    def test_constrain_noop_outside_context(self):
        x = jnp.ones((4, 4))
        assert sh.constrain(x, "residual") is x

    def test_constrain_applies_in_context(self):
        mesh = host_mesh()
        S = sh.make_strategy("fsdp", mesh)
        with sh.activation_constraints(mesh, S):
            out = jax.jit(lambda x: sh.constrain(x, "residual"))(jnp.ones((4, 8, 16)))
        assert out.shape == (4, 8, 16)


SAMPLE_HLO = """
HloModule test

%add (a: f32[], b: f32[]) -> f32[] {
  ROOT %r = f32[] add(f32[] %a, f32[] %b)
}

%cond.1 (arg: (s32[], f32[128])) -> pred[] {
  %ivar = s32[] get-tuple-element(%arg), index=0
  %limit = s32[] constant(12)
  ROOT %lt = pred[] compare(%ivar, %limit), direction=LT
}

%body.1 (arg: (s32[], f32[128])) -> (s32[], f32[128]) {
  %x = f32[128]{0} get-tuple-element(%arg), index=1
  %ar = f32[128]{0} all-reduce(%x), channel_id=1, replica_groups=[2,4]<=[8], to_apply=%add
  ROOT %t = (s32[], f32[128]) tuple(%ivar2, %ar)
}

ENTRY %main (p: f32[128]) -> f32[128] {
  %ag = f32[1024]{0} all-gather(%p), channel_id=2, replica_groups=[1,8]<=[8], dimensions={0}
  %rs = f32[16]{0} reduce-scatter(%p), channel_id=3, replica_groups=[1,8]<=[8], to_apply=%add
  %cp = f32[128]{0} collective-permute(%p), channel_id=4, source_target_pairs={{0,1}}
  %w = (s32[], f32[128]) while(%init), condition=%cond.1, body=%body.1
  ROOT %out = f32[128]{0} get-tuple-element(%w), index=1
}
"""


class TestCollectiveParsing:
    def test_sample_module(self):
        stats = rl.parse_collectives(SAMPLE_HLO)
        assert stats.op_counts == {"all-reduce": 1, "all-gather": 1, "reduce-scatter": 1, "collective-permute": 1}
        ops = {o["kind"]: o for o in stats.ops}
        # all-reduce inside while body: trips auto-detected = 12
        assert ops["all-reduce"]["trips"] == 12
        assert ops["all-reduce"]["wire"] == pytest.approx(2 * (3 / 4) * 128 * 4 * 12)
        # all-gather: result is gathered output (1024 f32), ring (g-1)/g
        assert ops["all-gather"]["wire"] == pytest.approx((7 / 8) * 1024 * 4)
        # reduce-scatter: result is the shard -> input = shard * g
        assert ops["reduce-scatter"]["wire"] == pytest.approx((7 / 8) * 16 * 8 * 4)
        assert ops["collective-permute"]["wire"] == pytest.approx(128 * 4)

    def test_real_lowered_module_trips(self):
        # scan body collective x trip count, measured end-to-end through jit
        mesh = host_mesh()

        def f(x):
            def body(c, _):
                return c * 2.0, ()

            c, _ = jax.lax.scan(body, x, None, length=9)
            return c.sum()

        txt = jax.jit(f).lower(jnp.ones((8, 8))).compile().as_text()
        stats = rl.parse_collectives(txt)  # no collectives on 1 device
        assert stats.per_chip_wire_bytes == 0.0

    def test_group_size_list_form(self):
        line = "%ar = f32[64]{0} all-reduce(%x), replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%add"
        module = "ENTRY %m (p: f32[64]) -> f32[64] {\n  " + line + "\n}\n"
        stats = rl.parse_collectives(module)
        assert stats.ops[0]["group"] == 4


class TestModelFlops:
    def test_train_flops_close_to_6nd(self):
        cfg = get_config("deepseek-7b")
        shape = SHAPES["train_4k"]
        got = rl.model_flops_for(cfg, shape)
        n = cfg.param_count() - 2 * cfg.vocab * cfg.d_model
        lower = 6 * n * shape.seq_len * shape.global_batch
        assert got >= lower  # attention + lm head add on top
        assert got < 1.6 * lower

    def test_moe_uses_active_params(self):
        mix = get_config("mixtral-8x7b")
        dense_equiv = rl.model_flops_for(mix, SHAPES["train_4k"])
        assert mix.active_param_count() < 0.4 * mix.param_count()
        n_act = mix.active_param_count() - 2 * mix.vocab * mix.d_model
        assert dense_equiv < 6 * n_act * SHAPES["train_4k"].tokens_per_step * 1.6

    def test_decode_tokens(self):
        assert SHAPES["decode_32k"].tokens_per_step == 128
        assert SHAPES["train_4k"].tokens_per_step == 4096 * 256

    def test_kernel_credit_positive_for_attention_archs(self):
        cfg = get_config("qwen3-4b")
        credit = rl.kernel_credit_bytes(cfg, SHAPES["train_4k"], 256)
        assert credit > 0
        ssm = get_config("rwkv6-7b")
        credit_ssm = rl.kernel_credit_bytes(ssm, SHAPES["train_4k"], 256)
        assert credit_ssm > 0  # wkv state credit

    def test_sliding_window_reduces_credit(self):
        full = get_config("deepseek-7b")
        win = get_config("mixtral-8x7b")  # SWA 4096 over 32k
        c_full = rl.attention_scan_overhead_bytes(full, SHAPES["prefill_32k"], 256)
        c_win = rl.attention_scan_overhead_bytes(win, SHAPES["prefill_32k"], 256)
        # same-order models, but windowed context is 8x smaller at 32k
        assert c_win < c_full


class TestDryrunSmokeOnHostMesh:
    """Lower + compile a reduced config on the 1x1 host mesh — the same code
    path as the 512-device dry-run, minus the forced device count."""

    @pytest.mark.parametrize("arch", ["qwen3-4b", "mixtral-8x7b", "rwkv6-7b", "whisper-tiny"])
    def test_train_step_lowers(self, arch):
        import dataclasses

        from repro.configs.base import ShapeConfig, input_specs
        from repro.launch import steps
        from repro.optim import adamw

        cfg = get_smoke(arch)
        shape = ShapeConfig("smoke", seq_len=32, global_batch=2, kind="train")
        mesh = host_mesh()
        fn, args = steps.make_step(cfg, mesh, shape, adamw.OptimizerConfig())
        compiled = fn.lower(*args).compile()
        assert compiled.cost_analysis() is not None

    @pytest.mark.parametrize("arch", ["qwen3-4b", "hymba-1.5b"])
    def test_decode_step_lowers(self, arch):
        from repro.configs.base import ShapeConfig
        from repro.launch import steps

        cfg = get_smoke(arch)
        shape = ShapeConfig("smoke-dec", seq_len=64, global_batch=2, kind="decode")
        mesh = host_mesh()
        fn, args = steps.make_step(cfg, mesh, shape)
        compiled = fn.lower(*args).compile()
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes >= 0
