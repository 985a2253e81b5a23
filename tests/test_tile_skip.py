"""DynaTran tile skipping must be EXACT: the skipping datapath
(``KernelPolicy.skip=True``) and its mask-only twin (``skip=False``) are the
same lowering and must agree bitwise — at the kernel level (paged attention
ref + Pallas, block-sparse FFN), through the full paged decode/prefill steps
for every cache flavour (full / ring / int8), through the continuous serve
engine, and under tensor parallelism on a device mesh.

Runs on an emulated mesh for the TP half:
XLA_FLAGS=--xla_force_host_platform_device_count=8 (skips below 2 devices
unless REQUIRE_MULTIDEVICE is set).
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core.dynatran import SITES, SparsityConfig, ThresholdCalculator, TransferCurve
from repro.core.policy import KernelPolicy
from repro.kernels.paged_attention import paged_decode_attention
from repro.models import transformer as tfm
from repro.models import zoo
from repro.models.attention import paged_skip_decode_attention
from repro.models.kvcache import PageAllocator, PagedLayout

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 2 and not os.environ.get("REQUIRE_MULTIDEVICE"),
    reason="needs >= 2 devices (set XLA_FLAGS=--xla_force_host_platform_device_count=8)",
)

SP = SparsityConfig(mode="dynatran", sites=("ffn_act", "attn_out", "kv"), block=16)
# tau_kv sits near the median per-position max|k| of the tiny model
# (measured ~1.25), so roughly half the cached positions go dead
TAUS = {"ffn_act": 0.05, "attn_out": 0.02, "kv": 1.5}
POL_SKIP = KernelPolicy.from_config(SP, TAUS, skip=True)
POL_MASK = KernelPolicy.from_config(SP, TAUS, skip=False)


def tiny_cfg(**kw):
    base = dict(
        name="tiny-skip", family="dense", layers=2, d_model=64, heads=4, kv_heads=2,
        d_ff=128, vocab=128, remat="none", sparsity=SP,
    )
    base.update(kw)
    return ModelConfig(**base)


def sliding_cfg(**kw):
    return tiny_cfg(attention_pattern=("sliding", "full"), window=8, attn_logit_cap=50.0, **kw)


def make_tables(layout: PagedLayout, batch: int, slack: int = 4):
    allocs = {k: PageAllocator(batch * layout.budget(k) + 1 + slack, layout.page_size) for k in layout.kinds}
    tables = {
        k: jnp.asarray(np.stack([allocs[k].alloc(i, layout.budget(k)) for i in range(batch)]), jnp.int32)
        for k in layout.kinds
    }
    return tables, {k: allocs[k].num_pages for k in layout.kinds}


def linear_calculator() -> ThresholdCalculator:
    """Real (non-identity) transfer curves: tau rises linearly with rho, so a
    nonzero target_rho resolves to nonzero thresholds at every site.  The
    "kv" curve reaches past the tiny model's per-position max|k| median so a
    mid-range rho genuinely kills cached positions."""
    rhos = jnp.linspace(0.0, 1.0, 9)
    return ThresholdCalculator({
        s: TransferCurve(taus=jnp.linspace(0.0, 2.5 if s == "kv" else 0.3, 9), rhos=rhos)
        for s in SITES
    })


# ---------------------------------------------------------------------------
# kernel level: reference paged attention with occupancy
# ---------------------------------------------------------------------------


def _attn_case(seed, b=2, maxp=4, p=4, hkv=2, g=2, d=16, density=0.5, window=None):
    rng = np.random.default_rng(seed)
    h = hkv * g
    q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, maxp, p, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, maxp, p, hkv, d)), jnp.float32)
    occ = jnp.asarray(rng.random(size=(b, maxp, p)) < density)
    lengths = jnp.asarray(rng.integers(1, maxp * p + 1, size=(b,)), jnp.int32)
    return q, k, v, occ, lengths


class TestRefKernelSkipVsMask:
    @pytest.mark.parametrize("window", [None, 8])
    @pytest.mark.parametrize("density", [0.0, 0.3, 1.0])
    def test_skip_equals_mask_bitwise(self, window, density):
        q, k, v, occ, lengths = _attn_case(0, density=density, window=window)
        skip = paged_skip_decode_attention(q, k, v, occ, lengths, window=window, skip=True)
        mask = paged_skip_decode_attention(q, k, v, occ, lengths, window=window, skip=False)
        np.testing.assert_array_equal(np.asarray(skip), np.asarray(mask))
        assert np.isfinite(np.asarray(skip)).all()

    def test_all_dead_is_finite_and_attends_self(self):
        """Every position dead: the query's own slot stays live, so the row
        attends exactly its own K/V (softmax over one key)."""
        q, k, v, occ, _ = _attn_case(1, density=0.0)
        lengths = jnp.asarray([1, 5], jnp.int32)
        out = paged_skip_decode_attention(q, k, v, jnp.zeros_like(occ), lengths, skip=True)
        assert np.isfinite(np.asarray(out)).all()
        # row 0, length 1: only key in the cache is position 0 — output == v[pos 0]
        want = np.asarray(v)[0, 0, 0]  # [Hkv, D]
        got = np.asarray(out)[0, 0].reshape(2, 2, 16).mean(1)  # avg the G identical? no:
        # each query head of a group attends the same single value row
        for hh in range(4):
            np.testing.assert_allclose(np.asarray(out)[0, 0, hh], want[hh // 2], rtol=1e-6)

    def test_all_live_matches_occupancy_blind_reference(self):
        from repro.models.attention import decode_attention

        q, k, v, occ, lengths = _attn_case(2, density=1.0)
        b, maxp, p, hkv, d = k.shape
        flat_k = k.reshape(b, maxp * p, hkv, d)
        flat_v = v.reshape(b, maxp * p, hkv, d)
        got = paged_skip_decode_attention(q, k, v, jnp.ones_like(occ), lengths, skip=True)
        want = decode_attention(q, flat_k, flat_v, lengths)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-6, atol=2e-6)


class TestRefKernelOccupancyProperty:
    """Hypothesis property: skip == mask bitwise for ANY occupancy pattern."""

    def test_random_occupancy_property(self):
        hyp = pytest.importorskip("hypothesis")
        given, settings, st = hyp.given, hyp.settings, hyp.strategies

        @settings(max_examples=25, deadline=None)
        @given(
            seed=st.integers(0, 2**31 - 1),
            density=st.sampled_from([0.0, 0.1, 0.5, 0.9, 1.0]),
            windowed=st.booleans(),
        )
        def prop(seed, density, windowed):
            window = 8 if windowed else None
            q, k, v, occ, lengths = _attn_case(seed, density=density, window=window)
            skip = paged_skip_decode_attention(q, k, v, occ, lengths, window=window, skip=True)
            mask = paged_skip_decode_attention(q, k, v, occ, lengths, window=window, skip=False)
            np.testing.assert_array_equal(np.asarray(skip), np.asarray(mask))

        prop()

    def test_deterministic_anchor_rows(self):
        """No-hypothesis fallback anchors: one all-dead row + one all-live
        row in the same batch (the extreme the property would find first)."""
        q, k, v, occ, lengths = _attn_case(3)
        occ = occ.at[0].set(False).at[1].set(True)
        skip = paged_skip_decode_attention(q, k, v, occ, lengths, skip=True)
        mask = paged_skip_decode_attention(q, k, v, occ, lengths, skip=False)
        np.testing.assert_array_equal(np.asarray(skip), np.asarray(mask))


class TestPallasKernelSkipVsMask:
    def test_skip_equals_mask_and_visits_fall(self):
        rng = np.random.default_rng(4)
        b, maxp, p, hkv, g, d = 2, 4, 4, 2, 2, 16
        num_pages = 9
        pool_k = jnp.asarray(rng.normal(size=(num_pages, p, hkv, d)), jnp.float32)
        pool_v = jnp.asarray(rng.normal(size=(num_pages, p, hkv, d)), jnp.float32)
        table = jnp.asarray(rng.permutation(num_pages - 1)[: b * maxp].reshape(b, maxp) + 1, jnp.int32)
        q = jnp.asarray(rng.normal(size=(b, 1, hkv * g, d)), jnp.float32)
        lengths = jnp.asarray([maxp * p, maxp * p - 3], jnp.int32)
        occ = jnp.asarray(rng.random(size=(num_pages, p)) < 0.2)

        o_skip, n_skip = paged_decode_attention(
            q, pool_k, pool_v, table, lengths, occupancy=occ, skip=True,
            with_visits=True, interpret=True,
        )
        o_mask, n_mask = paged_decode_attention(
            q, pool_k, pool_v, table, lengths, occupancy=occ, skip=False,
            with_visits=True, interpret=True,
        )
        np.testing.assert_array_equal(np.asarray(o_skip), np.asarray(o_mask))
        # the mask twin visits every in-length page; skipping visits fewer
        assert (np.asarray(n_skip) <= np.asarray(n_mask)).all()
        assert np.asarray(n_skip).sum() < np.asarray(n_mask).sum()


class TestFFNBlockSparse:
    def _case(self, seed, m=8, f=64, dout=32, tau=0.5):
        from repro.kernels.ops import ffn_block_sparse

        rng = np.random.default_rng(seed)
        h = np.asarray(rng.normal(size=(1, m, f)), np.float32)
        h = np.where(np.abs(h) >= tau, h, 0.0)  # already pruned, as _mlp does
        w = jnp.asarray(rng.normal(size=(f, dout)), jnp.float32)
        return ffn_block_sparse, jnp.asarray(h), w

    @pytest.mark.parametrize("backend", ["ref", "pallas"])
    def test_skip_equals_mask_bitwise(self, backend):
        fn, h, w = self._case(0)
        pol = dataclasses.replace(POL_SKIP, backend=backend)
        out_skip = fn(h, w, pol)
        out_mask = fn(h, w, dataclasses.replace(POL_MASK, backend=backend))
        np.testing.assert_array_equal(np.asarray(out_skip), np.asarray(out_mask))

    def test_matches_dense_matmul(self):
        fn, h, w = self._case(1)
        out = np.asarray(fn(h, w, POL_SKIP))
        want = np.asarray(h) @ np.asarray(w)
        np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)

    def test_all_dead_rows_give_exact_zero(self):
        fn, h, w = self._case(2)
        out = np.asarray(fn(jnp.zeros_like(h), w, POL_SKIP))
        np.testing.assert_array_equal(out, np.zeros_like(out))


# ---------------------------------------------------------------------------
# model level: full paged decode/prefill with occupancy threading
# ---------------------------------------------------------------------------


class TestPagedDecodeTileSkipParity:
    """skip=True decode must EXACTLY equal the skip=False masked reference at
    identical taus, for every cache flavour, with occupancy bits written by
    both the token scatter (decode) and the chunk scatter (prefill)."""

    def _run(self, cfg, pol, steps=10, prefill=5, b=2, p=4, max_len=32, seed=0):
        params = zoo.init_params(jax.random.PRNGKey(seed), cfg)
        layout = tfm.paged_layout(cfg, max_len, p)
        tables, num_pages = make_tables(layout, b)
        pools = tfm.init_paged_state(cfg, layout, num_pages)
        occ = tfm.init_paged_occupancy(cfg, layout, num_pages)
        ssm = tfm.init_paged_ssm(cfg, b)
        rng = np.random.default_rng(seed)
        prompt = rng.integers(1, cfg.vocab, size=(b, prefill)).astype(np.int32)
        toks = rng.integers(1, cfg.vocab, size=(b, steps)).astype(np.int32)
        outs = []
        logits, pools, occ, ssm = tfm.paged_prefill_chunk(
            params, cfg, layout, pools, tables,
            jnp.zeros((b,), jnp.int32), jnp.asarray(prompt),
            jnp.full((b,), prefill, jnp.int32),
            occupancy=occ, ssm=ssm, policy=pol,
        )
        outs.append(np.asarray(logits))
        for t in range(steps):
            lengths = jnp.full((b,), prefill + t, jnp.int32)
            logits, pools, occ, ssm = tfm.paged_decode_step(
                params, cfg, layout, pools, tables, lengths,
                jnp.asarray(toks[:, t : t + 1]),
                occupancy=occ, ssm=ssm, policy=pol,
            )
            outs.append(np.asarray(logits))
        # the bits must move: some cached position should actually be dead
        dead = sum(int((~np.asarray(o)).sum()) for o in jax.tree_util.tree_leaves(occ))
        return outs, dead

    @pytest.mark.parametrize(
        "cfg_fn",
        [tiny_cfg, sliding_cfg, lambda: tiny_cfg(kv_cache_dtype="int8"),
         lambda: sliding_cfg(kv_cache_dtype="int8")],
        ids=["full", "ring", "int8", "ring-int8"],
    )
    def test_skip_equals_mask_every_step(self, cfg_fn):
        cfg = cfg_fn()
        got, dead_skip = self._run(cfg, POL_SKIP)
        want, dead_mask = self._run(cfg, POL_MASK)
        for t, (g, w) in enumerate(zip(got, want)):
            np.testing.assert_array_equal(g, w, err_msg=f"step {t}")
        assert dead_skip == dead_mask
        assert dead_skip > 0, "tau_kv never marked a position dead — test is vacuous"

    def test_legacy_policy_ignores_occupancy(self):
        """skip=None (legacy dense datapath) must reproduce the occupancy-blind
        step bitwise even when occupancy arrays are threaded through."""
        cfg = tiny_cfg()
        pol_legacy = KernelPolicy.from_config(SP, TAUS, skip=None)
        got, _ = self._run(cfg, pol_legacy)

        params = zoo.init_params(jax.random.PRNGKey(0), cfg)
        layout = tfm.paged_layout(cfg, 32, 4)
        tables, num_pages = make_tables(layout, 2)
        pools = tfm.init_paged_state(cfg, layout, num_pages)
        ssm = tfm.init_paged_ssm(cfg, 2)
        rng = np.random.default_rng(0)
        prompt = rng.integers(1, cfg.vocab, size=(2, 5)).astype(np.int32)
        toks = rng.integers(1, cfg.vocab, size=(2, 10)).astype(np.int32)
        want = []
        logits, pools, _, ssm = tfm.paged_prefill_chunk(
            params, cfg, layout, pools, tables, jnp.zeros((2,), jnp.int32),
            jnp.asarray(prompt), jnp.full((2,), 5, jnp.int32), ssm=ssm, policy=pol_legacy,
        )
        want.append(np.asarray(logits))
        for t in range(10):
            logits, pools, _, ssm = tfm.paged_decode_step(
                params, cfg, layout, pools, tables, jnp.full((2,), 5 + t, jnp.int32),
                jnp.asarray(toks[:, t : t + 1]), ssm=ssm, policy=pol_legacy,
            )
            want.append(np.asarray(logits))
        for t, (g, w) in enumerate(zip(got, want)):
            np.testing.assert_array_equal(g, w, err_msg=f"step {t}")


# ---------------------------------------------------------------------------
# engine level: the serve path end to end
# ---------------------------------------------------------------------------


def make_engine(cfg, params, **kw):
    from repro.serve.engine import ContinuousServeConfig, ContinuousServeEngine

    defaults = dict(slots=2, max_len=64, page_size=4, prefill_chunk=4)
    calculator = kw.pop("calculator", linear_calculator())
    defaults.update(kw)
    return ContinuousServeEngine(cfg, params, ContinuousServeConfig(**defaults), calculator)


class TestEngineTileSkip:
    @pytest.fixture(scope="class")
    def setup(self):
        cfg = tiny_cfg(sparsity=dataclasses.replace(SP, target_rho=0.6))
        params = zoo.init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, cfg.vocab, size=9).tolist() for _ in range(4)]
        return cfg, params, prompts

    def test_skip_equals_mask_token_identical(self, setup):
        cfg, params, prompts = setup
        want = make_engine(cfg, params, tile_skip=False).generate(prompts, max_new_tokens=8)
        got = make_engine(cfg, params, tile_skip=True).generate(prompts, max_new_tokens=8)
        assert got == want

    def test_occupancy_allocated_only_when_tiled(self, setup):
        cfg, params, _ = setup
        assert make_engine(cfg, params, tile_skip=True).occupancy is not None
        assert make_engine(cfg, params, tile_skip=None).occupancy is None

    def test_occupancy_bits_actually_drop(self, setup):
        cfg, params, prompts = setup
        eng = make_engine(cfg, params, tile_skip=True)
        eng.generate(prompts, max_new_tokens=8)
        bits = [np.asarray(v) for v in jax.tree_util.tree_leaves(eng.occupancy)]
        assert bits and not all(b.all() for b in bits)  # some position went dead

    def test_rho_zero_matches_legacy_dense_engine(self, setup):
        """At rho=0 every tau is 0, no position is ever dead, and the tiled
        engine must emit exactly the legacy engine's tokens."""
        cfg, params, prompts = setup
        cfg0 = dataclasses.replace(cfg, sparsity=dataclasses.replace(SP, target_rho=0.0))
        legacy = make_engine(cfg0, params, tile_skip=None).generate(prompts, max_new_tokens=8)
        tiled = make_engine(cfg0, params, tile_skip=True).generate(prompts, max_new_tokens=8)
        assert tiled == legacy


@needs_mesh
class TestTPTileSkip:
    @pytest.fixture(scope="class")
    def setup(self):
        cfg = tiny_cfg(kv_heads=4, sparsity=dataclasses.replace(SP, target_rho=0.6))
        params = zoo.init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(1)
        prompts = [rng.integers(1, cfg.vocab, size=9).tolist() for _ in range(4)]
        return cfg, params, prompts

    def test_tp_skip_matches_single_device(self, setup):
        cfg, params, prompts = setup
        want = make_engine(cfg, params, tile_skip=True).generate(prompts, max_new_tokens=6)
        got = make_engine(cfg, params, tile_skip=True, tp=2).generate(prompts, max_new_tokens=6)
        assert got == want

    def test_tp_skip_equals_tp_mask(self, setup):
        cfg, params, prompts = setup
        want = make_engine(cfg, params, tile_skip=False, tp=2).generate(prompts, max_new_tokens=6)
        got = make_engine(cfg, params, tile_skip=True, tp=2).generate(prompts, max_new_tokens=6)
        assert got == want
