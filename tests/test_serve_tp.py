"""Tensor-parallel serving over a device mesh: the paged pools, gather/
scatter, and attention shard along the KV-head dim via shard_map while the
host-side scheduler stays global — and TP>1 decode is BITWISE-identical to
the single-device engine for every page kind.

Runs on an emulated mesh: XLA_FLAGS=--xla_force_host_platform_device_count=8.
With fewer than 2 visible devices the mesh tests skip — unless
REQUIRE_MULTIDEVICE is set (the CI multidevice lane), where missing devices
must FAIL, not skip: the lane exists to prove these tests ran.
"""
import dataclasses
import os

import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.launch.mesh import make_serve_mesh
from repro.models import zoo
from repro.serve.engine import ContinuousServeConfig, ContinuousServeEngine
from repro.serve.sampling import SamplingParams

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 2 and not os.environ.get("REQUIRE_MULTIDEVICE"),
    reason="needs >= 2 devices (set XLA_FLAGS=--xla_force_host_platform_device_count=8)",
)

PAGE = 4


def tiny_cfg(**kw):
    base = dict(
        name="tiny-tp", family="dense", layers=2, d_model=64, heads=4, kv_heads=4,
        d_ff=128, vocab=128, remat="none",
    )
    base.update(kw)
    return ModelConfig(**base)


def make_engine(cfg, params, **kw):
    defaults = dict(slots=2, max_len=64, page_size=PAGE, prefill_chunk=4)
    defaults.update(kw)
    return ContinuousServeEngine(cfg, params, ContinuousServeConfig(**defaults))


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_cfg()
    params = zoo.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab, size=9).tolist() for _ in range(4)]
    return cfg, params, prompts


@needs_mesh
class TestServeMesh:
    def test_make_serve_mesh_shape(self):
        mesh = make_serve_mesh(2)
        assert mesh.shape == {"data": 1, "model": 2}

    def test_too_many_shards_rejected(self):
        with pytest.raises(ValueError, match="devices"):
            make_serve_mesh(len(jax.devices()) + 1)

    def test_indivisible_heads_rejected(self, setup):
        cfg = tiny_cfg(heads=3, kv_heads=3)
        params = zoo.init_params(jax.random.PRNGKey(0), cfg)
        with pytest.raises(ValueError, match="divisible"):
            make_engine(cfg, params, tp=2)


@needs_mesh
class TestTPBitwise:
    """TP>1 must emit exactly the single-device engine's tokens: the pools
    shard per KV head, attention is exact per head, and the all_gather
    reassembling attention outputs is pure data movement."""

    def test_full_pages(self, setup):
        cfg, params, prompts = setup
        want = make_engine(cfg, params).generate(prompts, max_new_tokens=8)
        got = make_engine(cfg, params, tp=2).generate(prompts, max_new_tokens=8)
        assert got == want

    def test_ring_pages(self, setup):
        _, _, prompts = setup
        cfg = tiny_cfg(name="tiny-tp-ring", attention_pattern=("sliding", "full"), window=8)
        params = zoo.init_params(jax.random.PRNGKey(1), cfg)
        want = make_engine(cfg, params).generate(prompts, max_new_tokens=8)
        got = make_engine(cfg, params, tp=2).generate(prompts, max_new_tokens=8)
        assert got == want

    def test_int8_pages(self, setup):
        _, _, prompts = setup
        cfg = dataclasses.replace(tiny_cfg(), name="tiny-tp-int8", kv_cache_dtype="int8")
        params = zoo.init_params(jax.random.PRNGKey(2), cfg)
        want = make_engine(cfg, params).generate(prompts, max_new_tokens=8)
        got = make_engine(cfg, params, tp=2).generate(prompts, max_new_tokens=8)
        assert got == want

    def test_int8_ring_pages(self, setup):
        """int8 + ring combined: quantised scale pools shard on their Hkv
        dim alongside the q pools, ring addressing included."""
        _, _, prompts = setup
        cfg = tiny_cfg(
            name="tiny-tp-int8-ring", attention_pattern=("sliding", "full"), window=8,
            kv_cache_dtype="int8",
        )
        params = zoo.init_params(jax.random.PRNGKey(4), cfg)
        want = make_engine(cfg, params).generate(prompts, max_new_tokens=8)
        got = make_engine(cfg, params, tp=2).generate(prompts, max_new_tokens=8)
        assert got == want

    def test_hybrid_ssm_side_state(self, setup):
        """Hybrid models: the SSM side-state is computed replicated (every
        shard holds the identical recurrent state) while attention shards."""
        _, _, _ = setup
        cfg = ModelConfig(
            name="tiny-tp-hybrid", family="hybrid", layers=2, d_model=64, heads=4,
            kv_heads=4, d_ff=128, vocab=128, remat="none",
            attention_pattern=("sliding",), window=8,
            ssm_state=8, ssm_expand=2, ssm_conv=4,
        )
        params = zoo.init_params(jax.random.PRNGKey(3), cfg)
        rng = np.random.default_rng(3)
        prompts = [rng.integers(1, cfg.vocab, size=6).tolist() for _ in range(3)]
        want = make_engine(cfg, params).generate(prompts, max_new_tokens=6)
        got = make_engine(cfg, params, tp=2).generate(prompts, max_new_tokens=6)
        assert got == want

    @pytest.mark.skipif(len(jax.devices()) < 4 and not os.environ.get("REQUIRE_MULTIDEVICE"),
                        reason="needs >= 4 devices")
    def test_tp4(self, setup):
        cfg, params, prompts = setup
        want = make_engine(cfg, params).generate(prompts, max_new_tokens=8)
        got = make_engine(cfg, params, tp=4).generate(prompts, max_new_tokens=8)
        assert got == want

    def test_sampled_decode_window(self, setup):
        """Per-request sampling knobs stay runtime tensors under the mesh
        (keyed streams reproduce), and multi-step decode windows scan
        through the shard_map unchanged."""
        cfg, params, prompts = setup
        sp = SamplingParams(temperature=0.8, top_k=20, seed=7, max_new_tokens=8)
        want = make_engine(cfg, params, decode_window=3).generate(prompts, sampling=sp)
        got = make_engine(cfg, params, decode_window=3, tp=2).generate(prompts, sampling=sp)
        assert got == want

    def test_runtime_taus_no_recompile_under_mesh(self, setup):
        """DynaTran taus enter the sharded step as runtime scalars: changing
        rho between calls must not retrace the TP decode step."""
        from repro.core.dynatran import SparsityConfig

        _, _, prompts = setup
        cfg = dataclasses.replace(
            tiny_cfg(), sparsity=SparsityConfig(mode="dynatran", target_rho=0.2)
        )
        params = zoo.init_params(jax.random.PRNGKey(0), cfg)
        eng = make_engine(cfg, params, tp=2, prefix_caching=False)
        eng.generate([prompts[0]], max_new_tokens=4)
        traces = eng._decode._cache_size()
        eng._fixed_rho = 0.6  # runtime knob only — no retrace allowed
        eng.generate([prompts[1]], max_new_tokens=4)
        assert eng._decode._cache_size() == traces

    def test_use_pallas_under_mesh(self, setup):
        """The fused Pallas kernel is shard-local over KV heads: the TP
        engine runs it inside shard_map (interpret mode on CPU) and matches
        the single-device Pallas engine."""
        cfg, params, prompts = setup
        want = make_engine(cfg, params, use_pallas=True).generate(prompts, max_new_tokens=6)
        got = make_engine(cfg, params, use_pallas=True, tp=2).generate(prompts, max_new_tokens=6)
        assert got == want


@needs_mesh
class TestTPMemoryAndState:
    def test_pool_bytes_split_exactly(self, setup):
        cfg, params, _ = setup
        for tp in (1, 2):
            eng = make_engine(cfg, params, tp=tp)
            m = eng.metrics()
            assert m["tp"] == tp
            assert m["cache_bytes_per_shard"] * tp == m["cache_bytes"]

    def test_params_replicated_over_mesh(self, setup):
        """The engine places the caller's single-device weights on the mesh
        once, so no dispatch has to replicate them again."""
        cfg, params, _ = setup
        eng = make_engine(cfg, params, tp=2)
        for leaf in jax.tree_util.tree_leaves(eng.params):
            assert leaf.sharding.mesh == eng.mesh
            assert leaf.sharding.is_fully_replicated
            assert {s.device for s in leaf.addressable_shards} == set(eng.mesh.devices.flat)

    def test_int8_scale_pools_split_too(self, setup):
        cfg = dataclasses.replace(tiny_cfg(), kv_cache_dtype="int8")
        params = zoo.init_params(jax.random.PRNGKey(0), cfg)
        eng = make_engine(cfg, params, tp=2)
        assert eng.pools.shard_bytes() * 2 == eng.pools.bytes()

    def test_host_side_state_is_global(self, setup):
        """The allocator, page tables, and prefix cache never see the mesh:
        page ids are shard-invariant."""
        cfg, params, prompts = setup
        eng1 = make_engine(cfg, params)
        eng2 = make_engine(cfg, params, tp=2)
        for e in (eng1, eng2):
            e.generate(prompts[:2], max_new_tokens=6)
        a1, a2 = eng1.allocators["full"], eng2.allocators["full"]
        assert a1.num_pages == a2.num_pages
        assert a1.free_pages == a2.free_pages  # identical host-side schedule

    def test_prefix_cache_and_cow_under_tp(self, setup):
        """Shared-prefix linking and copy-on-write forks run on the global
        page ids; the device-side page copy fans out to every shard."""
        cfg, params, prompts = setup
        prompt = prompts[0][:8]  # exactly 2 pages
        ref = make_engine(cfg, params, slots=1, prefix_caching=False)
        want = ref.generate([prompt] * 2, max_new_tokens=6)
        eng = make_engine(cfg, params, slots=1, tp=2)
        a = eng.generate([prompt], max_new_tokens=6)[0]
        b = eng.generate([prompt], max_new_tokens=6)[0]
        assert [a, b] == want
        stats = eng.metrics()["prefix_cache"]
        assert stats["hits"] == 1 and stats["pages_shared"] == 2


class TestShardBytesUnsharded:
    def test_equals_total_on_one_device(self, setup):
        cfg, params, _ = setup
        eng = make_engine(cfg, params)
        assert eng.pools.shard_bytes() == eng.pools.bytes()


class TestRegressionGateLogic:
    """Unit checks on benchmarks/check_regression.py (the CI bench gate):
    parity flags fail with zero tolerance, throughput ratios gate at the
    tolerance, and a single-device run whose TP section legitimately
    skipped is not punished for the baseline's TP metrics."""

    def fresh(self, **over):
        result = {
            "analysis_clean": True,
            "bitwise_identical_rho0": True,
            "outputs_match_baseline": True,
            "speedup": 2.0,
            "baseline": {"tok_per_s": 100.0},
            "continuous": {"tok_per_s": 200.0},
            "ring": {"bitwise_identical_rho0": True, "ring_bytes_flat_in_max_len": True,
                     "tok_per_s": 150.0},
            "prefix_cache": {"tokens_identical_to_uncached": True,
                             "allocator_drained_at_shutdown": True,
                             "burst_tokens_identical": True, "burst_relinked_pages": 5,
                             "tok_per_s": 150.0},
            "tp": {"skipped": "needs >= 2 devices, have 1"},
            "families": {
                "rwkv6": {"tokens_match_dense": True, "state_bytes_flat_in_max_len": True,
                          "tok_per_s": 300.0, "slot_tok_per_s": 100.0},
                "whisper": {"tokens_match_dense": True, "allocator_drained": True,
                            "tok_per_s": 80.0},
            },
            "sparsity": {
                "tile_skip_exact": True,
                "rho05_vs_rho0": 1.2,
                "pallas_visits": {"strictly_decreasing": True},
            },
            "router": {
                "router_tokens_exact": True,
                "router_drain": True,
                "slo_ladder_ordered": True,
                "affinity_hit_rate": 0.6,
                "router2_vs_single": 0.5,
            },
            "tiering": {
                "tier_restore_exact": True,
                "restore_vs_replay": 1.5,
            },
            "speculative": {
                "spec_tokens_exact": True,
                "spec_vs_nonspec": 1.3,
            },
        }
        result.update(over)
        return result

    def baseline(self):
        return {"throughput_ratios": {"speedup": 1.0, "ring_vs_slot": 1.0,
                                      "tp2_vs_slot": 0.5, "rwkv6_vs_slot": 1.0,
                                      "rho05_vs_rho0": 1.0}}

    def test_tp_skipped_fresh_run_passes(self):
        from benchmarks.check_regression import check_parity, check_throughput

        fresh = self.fresh()
        assert check_parity(fresh) == []
        failures, _ = check_throughput(fresh, self.baseline(), 0.25)
        assert failures == []  # tp2_vs_slot absent but the section skipped

    def test_missing_nonskipped_metric_fails(self):
        from benchmarks.check_regression import check_throughput

        fresh = self.fresh(tp={"scaling": [], "bitwise_identical_tp": {}})
        failures, _ = check_throughput(fresh, self.baseline(), 0.25)
        assert any("tp2_vs_slot" in f for f in failures)

    def test_parity_flip_fails(self):
        from benchmarks.check_regression import check_parity

        fresh = self.fresh(tp={"bitwise_identical_tp": {"ring": False}, "scaling": []})
        assert any("ring pages" in f for f in check_parity(fresh))

    def test_throughput_regression_fails(self):
        from benchmarks.check_regression import check_throughput

        fresh = self.fresh(speedup=0.5)
        failures, _ = check_throughput(fresh, self.baseline(), 0.25)
        assert any("speedup regressed" in f for f in failures)

    def test_family_parity_flip_fails(self):
        """The DecodeState families' correctness claims are zero-tolerance
        parity flags: a flipped rwkv6/whisper flag fails the gate."""
        from benchmarks.check_regression import check_parity

        fresh = self.fresh()
        fresh["families"]["rwkv6"]["tokens_match_dense"] = False
        assert any("rwkv6_tokens_match_dense" in f for f in check_parity(fresh))
        fresh = self.fresh()
        del fresh["families"]["whisper"]["allocator_drained"]
        assert any("whisper_drained" in f for f in check_parity(fresh))

    def test_tile_skip_parity_flip_fails(self):
        """A tile-skipped run whose tokens diverged from the masked twin is a
        zero-tolerance failure, as is a visit counter that stopped falling."""
        from benchmarks.check_regression import check_parity

        fresh = self.fresh()
        fresh["sparsity"]["tile_skip_exact"] = False
        assert any("tile_skip_exact" in f for f in check_parity(fresh))
        fresh = self.fresh()
        fresh["sparsity"]["pallas_visits"]["strictly_decreasing"] = False
        assert any("sparsity_visits_decreasing" in f for f in check_parity(fresh))

    def test_analysis_clean_flip_fails(self):
        """A bench run whose in-process reprolint pass found violations (or
        stale baseline entries) fails the gate with zero tolerance — the
        bench gate and the lint-invariants CI lane must agree."""
        from benchmarks.check_regression import check_parity

        for bad in (False, None):
            fresh = self.fresh(analysis_clean=bad)
            assert any("analysis_clean" in f for f in check_parity(fresh)), bad

    def test_rho_ratio_hard_floor(self):
        """The rho=0.5 vs rho=0 tokens/s ratio has a HARD floor of 1.0 — a
        same-run ratio, so no machine tolerance applies.  At the floor,
        below it, or missing entirely: the gate fails."""
        from benchmarks.check_regression import check_parity

        assert check_parity(self.fresh()) == []
        for bad in (0.93, 1.0, None):
            fresh = self.fresh()
            fresh["sparsity"]["rho05_vs_rho0"] = bad
            assert any("rho05_vs_rho0" in f for f in check_parity(fresh)), bad

    def test_rho_ratio_tracked_in_trajectory(self):
        from benchmarks.check_regression import throughput_ratios

        assert throughput_ratios(self.fresh())["rho05_vs_rho0"] == 1.2

    def test_router_parity_flip_fails(self):
        """The router's placement-invisibility claims are zero-tolerance:
        token divergence, lossy drain, or a shed before the rho ladder
        saturates each fails the gate — as does a flag missing entirely."""
        from benchmarks.check_regression import check_parity

        for key, label in (
            ("router_tokens_exact", "router_tokens_exact"),
            ("router_drain", "router_drain"),
            ("slo_ladder_ordered", "router_slo_ladder_ordered"),
        ):
            for bad in (False, None):
                fresh = self.fresh()
                if bad is None:
                    del fresh["router"][key]
                else:
                    fresh["router"][key] = bad
                assert any(label in f for f in check_parity(fresh)), (key, bad)

    def test_router_affinity_hit_rate_must_be_positive(self):
        from benchmarks.check_regression import check_parity

        fresh = self.fresh()
        fresh["router"]["affinity_hit_rate"] = 0.0
        assert any("affinity hit" in f for f in check_parity(fresh))

    def test_tier_restore_parity_flip_fails(self):
        """A restored request whose tokens diverged from the straight
        decode / evict+replay run is a zero-tolerance failure — as is the
        flag missing entirely (e.g. the tiering section silently dropped)."""
        from benchmarks.check_regression import check_parity

        for bad in (False, None):
            fresh = self.fresh()
            if bad is None:
                del fresh["tiering"]["tier_restore_exact"]
            else:
                fresh["tiering"]["tier_restore_exact"] = bad
            assert any("tier_restore_exact" in f for f in check_parity(fresh)), bad

    def test_tier_ratio_hard_floor(self):
        """The restore-vs-replay ratio has a HARD same-run floor of 1.0 —
        a tier that does not beat re-prefilling is pure overhead.  At the
        floor, below it, or missing: the gate fails; above it, the ratio
        feeds the trajectory."""
        from benchmarks.check_regression import check_parity, throughput_ratios

        assert check_parity(self.fresh()) == []
        assert throughput_ratios(self.fresh())["tier_restore_vs_replay"] == 1.5
        for bad in (0.8, 1.0, None):
            fresh = self.fresh()
            fresh["tiering"]["restore_vs_replay"] = bad
            assert any("tier_restore_vs_replay" in f for f in check_parity(fresh)), bad

    def test_spec_parity_flip_fails(self):
        """A speculative run whose emitted streams diverged from the
        non-speculative engine is a zero-tolerance failure — as is the flag
        missing entirely (e.g. the speculative section silently dropped)."""
        from benchmarks.check_regression import check_parity

        for bad in (False, None):
            fresh = self.fresh()
            if bad is None:
                del fresh["speculative"]["spec_tokens_exact"]
            else:
                fresh["speculative"]["spec_tokens_exact"] = bad
            assert any("spec_tokens_exact" in f for f in check_parity(fresh)), bad

    def test_spec_ratio_hard_floor(self):
        """The spec-vs-nonspec tokens/s ratio has a HARD same-run floor of
        1.0 — speculation that does not beat one-token-per-dispatch decode
        is pure overhead.  At the floor, below it, or missing: the gate
        fails; above it, the ratio feeds the trajectory."""
        from benchmarks.check_regression import check_parity, throughput_ratios

        assert check_parity(self.fresh()) == []
        assert throughput_ratios(self.fresh())["spec_vs_nonspec"] == 1.3
        for bad in (0.9, 1.0, None):
            fresh = self.fresh()
            fresh["speculative"]["spec_vs_nonspec"] = bad
            assert any("spec_vs_nonspec" in f for f in check_parity(fresh)), bad

    def test_router_ratio_hard_floor(self):
        """The 2-replica vs single-engine tokens/s ratio has a HARD same-run
        floor (no machine tolerance): at the floor, below it, or missing,
        the gate fails; above it, the ratio feeds the trajectory."""
        from benchmarks.check_regression import check_parity, throughput_ratios

        assert check_parity(self.fresh()) == []
        assert throughput_ratios(self.fresh())["router2_vs_single"] == 0.5
        for bad in (0.1, 0.25, None):
            fresh = self.fresh()
            fresh["router"]["router2_vs_single"] = bad
            assert any("router2_vs_single" in f for f in check_parity(fresh)), bad


@needs_mesh
class TestPallasKernelShardLocal:
    """The Pallas gather and fused decode-attention kernels, called with
    shard-local operands inside shard_map, reproduce the head-slices of the
    unsharded kernel outputs."""

    def test_paged_gather_head_sharded(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.kernels.paged_attention import paged_gather

        mesh = make_serve_mesh(2)
        rng = np.random.default_rng(0)
        pool = rng.standard_normal((6, 4, 4, 8)).astype(np.float32)
        table = np.array([[1, 2, 0], [3, 4, 5]], np.int32)
        want = paged_gather(jax.numpy.asarray(pool), jax.numpy.asarray(table))
        spec = P(None, None, "model", None)
        f = jax.shard_map(
            lambda p, t: paged_gather(p, t), mesh=mesh,
            in_specs=(spec, P()), out_specs=P(None, None, "model", None),
            check_vma=False,
        )
        pool_s = jax.device_put(jax.numpy.asarray(pool), NamedSharding(mesh, spec))
        got = f(pool_s, jax.numpy.asarray(table))
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))

    def test_fused_attention_head_sharded(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.kernels.paged_attention import paged_decode_attention

        mesh = make_serve_mesh(2)
        rng = np.random.default_rng(1)
        pool_k = rng.standard_normal((6, 4, 4, 8)).astype(np.float32)
        pool_v = rng.standard_normal((6, 4, 4, 8)).astype(np.float32)
        table = np.array([[1, 2, 0], [3, 4, 5]], np.int32)
        lengths = np.array([9, 11], np.int32)
        q = rng.standard_normal((2, 1, 8, 8)).astype(np.float32)
        want = paged_decode_attention(
            jax.numpy.asarray(q), jax.numpy.asarray(pool_k), jax.numpy.asarray(pool_v),
            jax.numpy.asarray(table), jax.numpy.asarray(lengths),
        )
        pspec = P(None, None, "model", None)
        f = jax.shard_map(
            lambda qq, kk, vv, tt, ll: paged_decode_attention(qq, kk, vv, tt, ll),
            mesh=mesh,
            in_specs=(P(None, None, "model", None), pspec, pspec, P(), P()),
            out_specs=P(None, None, "model", None),
            check_vma=False,
        )
        put = lambda x, s: jax.device_put(jax.numpy.asarray(x), NamedSharding(mesh, s))
        got = f(
            put(q, P(None, None, "model", None)), put(pool_k, pspec), put(pool_v, pspec),
            jax.numpy.asarray(table), jax.numpy.asarray(lengths),
        )
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
