"""Request-lifecycle serve API: per-request SamplingParams through the
jitted step, streaming + cancellation, and refcounted shared-prefix page
caching with copy-on-write."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.models import zoo
from repro.serve.engine import ContinuousServeConfig, ContinuousServeEngine
from repro.serve.sampling import SamplingParams

PAGE = 4


def tiny_cfg(**kw):
    return ModelConfig(
        name="tiny-lifecycle", family="dense", layers=2, d_model=64, heads=2, kv_heads=2,
        d_ff=128, vocab=128, remat="none", **kw,
    )


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_cfg()
    params = zoo.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    sys_prompt = rng.integers(1, cfg.vocab, size=9).tolist()
    prompts = [sys_prompt + rng.integers(1, cfg.vocab, size=3).tolist() for _ in range(5)]
    return cfg, params, prompts


def make_engine(cfg, params, **kw):
    defaults = dict(slots=2, max_len=64, page_size=PAGE, prefill_chunk=4)
    defaults.update(kw)
    return ContinuousServeEngine(cfg, params, ContinuousServeConfig(**defaults))


def drained(engine) -> bool:
    return all(a.free_pages == a.num_pages - 1 for a in engine.allocators.values())


class TestPerRequestSampling:
    def test_mixed_policies_in_one_batch(self, setup):
        """Greedy and sampled requests share a decode batch; the greedy
        rows' tokens are unaffected by their sampled neighbours."""
        cfg, params, prompts = setup
        ref = make_engine(cfg, params, prefix_caching=False)
        greedy_want = ref.generate([prompts[0]], max_new_tokens=8)[0]
        eng = make_engine(cfg, params, prefix_caching=False)
        g = eng.submit(prompts[0], max_new_tokens=8)
        s = eng.submit(prompts[1], sampling=SamplingParams(temperature=1.0, seed=3, max_new_tokens=8))
        eng.run_until_complete()
        assert g.generated == greedy_want
        assert len(s.generated) == 8

    def test_seeded_sampling_reproducible_across_schedules(self, setup):
        """Same (seed, step) keys => identical sampled streams whether the
        request runs alone or contended with evictions+replay."""
        cfg, params, prompts = setup

        def sp(i):
            return SamplingParams(temperature=0.7, seed=i, max_new_tokens=8)

        ref = make_engine(cfg, params, slots=1, prefix_caching=False)
        want = [ref.generate([p], sampling=sp(i))[0] for i, p in enumerate(prompts)]
        eng = make_engine(cfg, params, slots=3, num_pages=14)  # page pressure -> evictions
        reqs = [eng.submit(p, sampling=sp(i)) for i, p in enumerate(prompts)]
        eng.run_until_complete()
        assert [r.generated for r in reqs] == want

    def test_stop_token_set(self, setup):
        cfg, params, prompts = setup
        eng = make_engine(cfg, params)
        full = eng.generate([prompts[0]], max_new_tokens=8)[0]
        stops = {full[2], full[5]}
        eng2 = make_engine(cfg, params)
        got = eng2.generate([prompts[0]], sampling=SamplingParams(stop=stops, max_new_tokens=8))[0]
        # earliest stop wins, included: a stop token may occur before index 2
        cut = next(j for j, t in enumerate(full) if t in stops)
        assert cut <= 2 and got == full[: cut + 1] and got[-1] in stops

    def test_eos_id_alias_still_works(self, setup):
        cfg, params, prompts = setup
        eng = make_engine(cfg, params)
        full = eng.generate([prompts[0]], max_new_tokens=8)[0]
        eng2 = make_engine(cfg, params)
        got = eng2.generate([prompts[0]], max_new_tokens=8, eos_id=full[2])[0]
        cut = full.index(full[2])  # its first occurrence, which may precede index 2
        assert got == full[: cut + 1] and got[-1] == full[2]


class TestStreamingAndCancel:
    def test_stream_yields_full_generation(self, setup):
        cfg, params, prompts = setup
        eng = make_engine(cfg, params)
        want = make_engine(cfg, params).generate([prompts[0]], max_new_tokens=8)[0]
        handle = eng.submit(prompts[0], max_new_tokens=8)
        assert list(handle.tokens()) == want
        assert handle.done
        eng.drop_prefix_cache()
        assert drained(eng)

    def test_stream_interleaves_with_other_requests(self, setup):
        cfg, params, prompts = setup
        eng = make_engine(cfg, params, slots=2)
        h1 = eng.submit(prompts[0], max_new_tokens=6)
        h2 = eng.submit(prompts[1], max_new_tokens=6)
        assert len(list(h1.tokens())) == 6
        eng.run_until_complete()
        assert len(h2.generated) == 6

    def test_cancel_mid_stream_releases_pages(self, setup):
        cfg, params, prompts = setup
        eng = make_engine(cfg, params, prefix_caching=False)
        h1 = eng.submit(prompts[0], max_new_tokens=16)
        h2 = eng.submit(prompts[1], max_new_tokens=4)
        got = []
        for t in h1.tokens():
            got.append(t)
            if len(got) == 3:
                h1.cancel()
        assert h1.cancelled and h1.done and len(got) <= 4  # nothing after cancel
        eng.run_until_complete()
        assert len(h2.generated) == 4  # peers unaffected
        assert drained(eng)

    def test_cancel_queued_request(self, setup):
        cfg, params, prompts = setup
        eng = make_engine(cfg, params, slots=1)
        h1 = eng.submit(prompts[0], max_new_tokens=4)
        h2 = eng.submit(prompts[1], max_new_tokens=4)  # queued behind h1
        h2.cancel()
        assert h2.done and list(h2.tokens()) == []
        eng.run_until_complete()
        assert len(h1.generated) == 4
        eng.drop_prefix_cache()
        assert drained(eng)
        assert sum(r.cancelled for r in eng.requests) == 1

    def test_cancel_mid_prefill_releases_pages(self, setup):
        cfg, params, prompts = setup
        eng = make_engine(cfg, params, prefill_chunk=2, prefix_caching=False)
        h = eng.submit(prompts[0], max_new_tokens=4)  # 12-token prompt, chunk 2
        eng.step()  # admission + first prefill chunk only
        assert h.slot is not None and not h.ready
        h.cancel()
        assert drained(eng)
        eng.run_until_complete()

    def test_cancel_evicted_request(self, setup):
        cfg, params, prompts = setup
        eng = make_engine(cfg, params, slots=3, num_pages=14, prefix_caching=False)
        reqs = [eng.submit(p, max_new_tokens=10) for p in prompts]
        evicted = None
        for _ in range(200):
            eng.step()
            evicted = next((r for r in reqs if r.evictions and r.slot is None and not r.done), None)
            if evicted is not None:
                break
        assert evicted is not None, "workload never evicted anyone"
        evicted.cancel()
        eng.run_until_complete()
        assert evicted.done and not evicted.ready
        assert drained(eng)

    def test_cancel_is_idempotent_and_ignores_finished(self, setup):
        cfg, params, prompts = setup
        eng = make_engine(cfg, params)
        h = eng.submit(prompts[0], max_new_tokens=3)
        eng.run_until_complete()
        t0 = h.finish_time
        h.cancel()
        assert not h.cancelled and h.finish_time == t0


class TestPrefixCache:
    def test_shared_prefix_identical_tokens_and_fewer_pages(self, setup):
        """The acceptance bench in miniature: a shared-system-prompt
        workload (one warm-up fills the cache, then a concurrent burst
        links it) produces identical tokens with caching on/off while the
        cached burst holds measurably fewer pages."""
        cfg, params, _ = setup
        rng = np.random.default_rng(3)
        system = rng.integers(1, cfg.vocab, size=16).tolist()  # 4 full pages
        workload = [system + rng.integers(1, cfg.vocab, size=2).tolist() for _ in range(5)]
        runs = {}
        for caching in (False, True):
            eng = make_engine(cfg, params, slots=4, prefix_caching=caching)
            outs = [eng.generate([workload[0]], max_new_tokens=6)[0]]  # warm-up
            eng._peak_pages_in_use = 0  # measure the burst phase alone
            reqs = [eng.submit(p, max_new_tokens=6) for p in workload[1:]]
            eng.run_until_complete()
            outs += [r.generated for r in reqs]
            runs[caching] = (outs, eng.metrics())
        assert runs[True][0] == runs[False][0]
        m = runs[True][1]
        assert m["prefix_cache"]["hit_rate"] > 0
        assert m["prefix_cache"]["pages_shared"] > 0
        assert m["peak_pages_in_use"] < runs[False][1]["peak_pages_in_use"]

    def test_page_aligned_prompt_cow_fork(self, setup):
        """A fully page-aligned prompt repeated: the second request shares
        every prompt page, recomputes only the last token into a forked
        page, and still emits identical tokens."""
        cfg, params, prompts = setup
        prompt = prompts[0][:8]  # 8 tokens = exactly 2 pages of 4
        ref = make_engine(cfg, params, prefix_caching=False)
        want = ref.generate([prompt] * 2, max_new_tokens=6)
        eng = make_engine(cfg, params, slots=1)
        a = eng.generate([prompt], max_new_tokens=6)[0]
        b = eng.generate([prompt], max_new_tokens=6)[0]
        assert [a, b] == want
        stats = eng.metrics()["prefix_cache"]
        assert stats["hits"] == 1 and stats["pages_shared"] == 2

    def test_cache_survives_owner_and_drops_on_demand(self, setup):
        cfg, params, prompts = setup
        eng = make_engine(cfg, params, slots=1)
        eng.generate([prompts[0]], max_new_tokens=4)
        alloc = eng.allocators["full"]
        assert not drained(eng)  # prompt pages retained by the cache
        assert eng.prefix_cache.cached_pages == len(prompts[0]) // PAGE
        assert all(alloc.refcount(p) >= 1 for p in alloc.allocated)
        eng.drop_prefix_cache()
        assert drained(eng)

    def test_reclaim_under_pressure_prefers_cache_over_eviction(self, setup):
        """A full cache gives its pages back to new admissions before any
        live request is evicted."""
        cfg, params, prompts = setup
        eng = make_engine(cfg, params, slots=1, num_pages=9)  # 8 usable pages
        outs = [eng.generate([p], max_new_tokens=4)[0] for p in prompts]
        reqs = [r for r in eng.requests]
        assert sum(r.evictions for r in reqs) == 0
        ref = make_engine(cfg, params, slots=1, num_pages=9, prefix_caching=False)
        assert outs == [ref.generate([p], max_new_tokens=4)[0] for p in prompts]

    def test_eviction_replay_via_own_cached_prefix(self, setup):
        """An evicted request re-admitted through the prefix cache replays
        bit-exactly (its own prompt pages are the cache hit)."""
        cfg, params, prompts = setup
        ref = make_engine(cfg, params, slots=1, prefix_caching=False)
        want = [ref.generate([p], max_new_tokens=10)[0] for p in prompts]
        eng = make_engine(cfg, params, slots=3, num_pages=14)
        reqs = [eng.submit(p, max_new_tokens=10) for p in prompts]
        eng.run_until_complete()
        assert sum(r.evictions for r in reqs) > 0, "workload never evicted anyone"
        assert [r.generated for r in reqs] == want

    def test_sampled_decode_window_matches_single_step(self, setup):
        """Multi-step decode windows advance the (seed, step) key inside
        the scan, so sampled streams match single-step scheduling."""
        cfg, params, prompts = setup

        def sp(i):
            return SamplingParams(temperature=0.8, top_k=30, seed=i, max_new_tokens=9)

        one = make_engine(cfg, params)
        want = [one.submit(p, sampling=sp(i)) for i, p in enumerate(prompts)]
        one.run_until_complete()
        win = make_engine(cfg, params, decode_window=3)
        got = [win.submit(p, sampling=sp(i)) for i, p in enumerate(prompts)]
        win.run_until_complete()
        assert [r.generated for r in got] == [r.generated for r in want]

    def test_disabled_on_ring_layouts(self, setup):
        """Ring pages are per-sequence (content depends on the write
        cursor): sliding-window layouts must not share prefixes."""
        cfg, params, _ = setup
        ring_cfg = tiny_cfg(attention_pattern=("sliding", "full"), window=8)
        ring_params = zoo.init_params(jax.random.PRNGKey(1), ring_cfg)
        eng = make_engine(ring_cfg, ring_params, max_len=32)
        assert not eng.prefix_caching and eng.prefix_cache is None
        assert eng.metrics()["prefix_cache"] is None

    def test_disabled_under_adaptive_rho(self, setup):
        """K/V depend on the DynaTran taus: pages filled at one rho must
        not be linked by a request arriving at another, so ADAPTIVE rho
        disables the cache.  A fixed rho keeps taus constant for the
        engine's lifetime, so sharing stays sound there."""
        cfg, _, _ = setup
        from repro.core.dynatran import SparsityConfig

        dyn = dataclasses.replace(cfg, sparsity=SparsityConfig(mode="dynatran", target_rho=0.3))
        params = zoo.init_params(jax.random.PRNGKey(0), dyn)
        adaptive = make_engine(dyn, params, adaptive_rho=True)
        assert not adaptive.prefix_caching and adaptive.prefix_cache is None
        fixed = make_engine(dyn, params, target_rho=0.3)
        assert fixed.prefix_caching

    def test_evicted_request_purges_its_pending_cow_copies(self, setup):
        """A queued COW fork whose destination page is freed (evict/cancel)
        must not survive to clobber a later owner of that page."""
        cfg, params, prompts = setup
        prompt = prompts[0][:8]  # page-aligned: re-admission forks its boundary page
        eng = make_engine(cfg, params, slots=1)
        eng.generate([prompt], max_new_tokens=4)  # fill the cache
        h = eng.submit(prompt, max_new_tokens=4)
        eng.sched.admit_ready()  # links prefix + queues the boundary fork
        assert eng.sched.pending_copies
        fork_dst = {d for _, d in eng.sched.pending_copies}
        assert fork_dst <= set(h.tables["full"])
        h.cancel()  # frees the fork destination
        assert not eng.sched.pending_copies, "stale copy survived _drop_pages"
        eng.run_until_complete()

    def test_disabled_on_hybrid_ssm_layouts(self):
        """Hybrid-SSM side-state is per-slot recurrent state, not a pure
        function of the token prefix: the cache must auto-disable."""
        from repro import configs

        cfg = configs.get_smoke("hymba-1.5b")
        params = zoo.init_params(jax.random.PRNGKey(0), cfg)
        eng = ContinuousServeEngine(
            cfg, params, ContinuousServeConfig(slots=2, max_len=32, page_size=4, prefill_chunk=4)
        )
        assert cfg.ssm_state and not eng.prefix_caching and eng.prefix_cache is None

    def test_same_wave_burst_dedupes_mid_prefill(self, setup):
        """A COLD same-tick burst of identical prompts: pages register as
        each fills and peers relink them mid-prefill (vLLM-style), so the
        wave holds fewer pages at prefill completion than the uncached run
        while emitting identical tokens."""
        cfg, params, _ = setup
        rng = np.random.default_rng(7)
        system = rng.integers(1, cfg.vocab, size=16).tolist()  # 4 full pages
        tails = [rng.integers(1, cfg.vocab, size=2).tolist() for _ in range(4)]
        runs = {}
        for caching in (False, True):
            eng = make_engine(cfg, params, slots=4, prefix_caching=caching)
            reqs = [eng.submit(system + t, max_new_tokens=4) for t in tails]
            at_ready = None
            for _ in range(10_000):
                if all(r.done for r in reqs):
                    break
                eng.step()
                if at_ready is None and all(r.ready or r.done for r in reqs):
                    a = eng.allocators["full"]
                    at_ready = a.num_pages - 1 - a.free_pages
            runs[caching] = ([r.generated for r in reqs], at_ready, eng)
        assert runs[True][0] == runs[False][0]
        assert runs[True][1] < runs[False][1]
        assert runs[True][2].metrics()["prefix_cache"]["relinked_pages"] > 0

    def test_refresh_skip_ahead_forks_boundary_page(self, setup):
        """Scheduler-level anchor for ``refresh_prefix``: a mid-prefill
        request whose whole (page-aligned) prompt got cached by a peer
        links the chain, skips prefill to the last prompt token, and forks
        the boundary page (device copy queued) instead of sharing it."""
        from repro.models.kvcache import PageAllocator, PrefixCache
        from repro.serve.scheduler import ContinuousScheduler, Request

        alloc = PageAllocator(8, PAGE)
        cache = PrefixCache(alloc)
        s = ContinuousScheduler(2, {"full": alloc}, {"full": 8}, 64, prefix_cache=cache)
        prompt = list(range(100, 108))  # exactly 2 pages
        chain = alloc.alloc(99, 2)
        cache.insert(prompt, chain)
        alloc.free(99)  # peer finished; pages survive via retention refs
        req = Request(rid=1, prompt=prompt, max_new_tokens=1)
        s.submit(req)
        assert s.admit_ready() == [req]  # links the chain at admission...
        s.evict(req)
        cache_before = cache.cached_pages
        # ...so rebuild a mid-prefill request that MISSED the cache: fresh
        # pages, prefill_pos 0, as if admitted before the peer registered
        req2 = Request(rid=2, prompt=prompt, max_new_tokens=1)
        req2.slot = 0
        s.active[0] = req2
        req2.tables["full"] = alloc.alloc(2, 3)
        s.refresh_prefix(req2)
        assert req2.prefill_pos == len(prompt) - 1  # skipped to the last token
        assert req2.tables["full"][0] == chain[0]  # linked page 0
        assert req2.tables["full"][1] != chain[1]  # boundary page forked
        assert (chain[1], req2.tables["full"][1]) in s.pending_copies
        assert all(src != dst for src, dst in s.pending_copies)
        assert cache.cached_pages == cache_before  # fork never consumed the chain

    def test_refresh_fork_under_pool_pressure_aborts_cleanly(self, setup):
        """Pool dry at the boundary fork: the chain segment is PINNED while
        ``_alloc_pages`` reclaims cache entries, so reclaim can never free
        (or hand out as the fork destination) a page refresh is about to
        link or copy from — the skip aborts, books stay balanced."""
        from repro.models.kvcache import PageAllocator, PrefixCache
        from repro.serve.scheduler import ContinuousScheduler, Request

        alloc = PageAllocator(7, PAGE)  # trash + 6 usable
        cache = PrefixCache(alloc)
        s = ContinuousScheduler(2, {"full": alloc}, {"full": 8}, 64, prefix_cache=cache)
        prompt = list(range(100, 108))
        chain = alloc.alloc(99, 2)
        cache.insert(prompt, chain)
        alloc.free(99)
        req = Request(rid=1, prompt=prompt, max_new_tokens=1)
        req.slot = 0
        s.active[0] = req
        req.tables["full"] = alloc.alloc(1, 3)
        alloc.alloc(2, alloc.free_pages)  # a peer holds every remaining page
        assert alloc.free_pages == 0
        s.refresh_prefix(req)
        # the fork could not allocate: no skip, prefill continues normally,
        # and nothing points at a freed page
        assert req.prefill_pos == 0 and not req.ready
        assert all(src != dst for src, dst in s.pending_copies)
        owned = set(req.tables["full"])
        assert all(alloc.refcount(pg) >= 1 for pg in owned)
        # conservation: every page is either free or has a live reference
        assert len(alloc.allocated) + alloc.free_pages == alloc.num_pages - 1

    def test_int8_pages_are_shareable(self, setup):
        """int8 quantisation is per-position, so quantised prefix pages are
        still a pure function of the token prefix — shareable, and token
        streams stay identical with caching on."""
        cfg, params, prompts = setup
        q_cfg = dataclasses.replace(tiny_cfg(), kv_cache_dtype="int8")
        q_params = zoo.init_params(jax.random.PRNGKey(0), q_cfg)
        ref = make_engine(q_cfg, q_params, slots=1, prefix_caching=False)
        want = [ref.generate([p], max_new_tokens=4)[0] for p in prompts[:3]]
        eng = make_engine(q_cfg, q_params, slots=1)
        assert eng.prefix_caching
        got = [eng.generate([p], max_new_tokens=4)[0] for p in prompts[:3]]
        assert got == want
        assert eng.metrics()["prefix_cache"]["hits"] >= 1
