"""Continuous-batching scheduler: admission order, no starvation, rho
controller monotonicity, engine equivalence with the dense baseline."""
import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.models import zoo
from repro.models.kvcache import PageAllocator
from repro.serve.engine import ContinuousServeConfig, ContinuousServeEngine, ServeConfig, ServeEngine
from repro.serve.scheduler import ContinuousScheduler, Request, RhoController, summarize


def make_req(rid, prompt_len=8, max_new=8):
    return Request(rid=rid, prompt=list(range(1, prompt_len + 1)), max_new_tokens=max_new)


def make_sched(slots=2, num_pages=17, page_size=4, maxp=4):
    return ContinuousScheduler(
        slots, {"full": PageAllocator(num_pages, page_size)}, {"full": maxp}, maxp * page_size
    )


def make_ring_sched(slots=2, num_pages=9, page_size=4, budget=3, max_len=64):
    return ContinuousScheduler(
        slots, {"ring": PageAllocator(num_pages, page_size)}, {"ring": budget}, max_len
    )


class TestAdmission:
    def test_fifo_order(self):
        s = make_sched(slots=2)
        reqs = [make_req(i) for i in range(4)]
        for r in reqs:
            s.submit(r)
        admitted = s.admit_ready()
        assert [r.rid for r in admitted] == [0, 1]  # head-of-queue first
        assert [r.rid for r in s.queue] == [2, 3]

    def test_admission_blocks_on_pages_not_just_slots(self):
        s = make_sched(slots=4, num_pages=5)  # 4 usable pages
        for i in range(3):
            s.submit(make_req(i, prompt_len=8))  # needs 3 pages each (8+1 tokens / 4)
        admitted = s.admit_ready()
        assert len(admitted) == 1  # second request cannot fit its replay
        assert s.queue_depth == 2

    def test_oversized_request_rejected(self):
        s = make_sched(maxp=2, page_size=4)
        with pytest.raises(ValueError):
            s.submit(make_req(0, prompt_len=8, max_new=8))  # 16 > 2*4


class TestEviction:
    def test_youngest_evicted_and_requeued_at_front(self):
        s = make_sched(slots=2, num_pages=7)
        old, young = make_req(0, prompt_len=8), make_req(1, prompt_len=8)
        s.submit(old)
        s.submit(young)
        assert len(s.admit_ready()) == 2
        old.cache_len = 12  # old needs a 4th page; pool is empty -> evict young
        assert s.grow(old) is True
        assert young.slot is None and s.queue[0] is young
        assert old.slot is not None

    def test_oldest_never_evicted(self):
        s = make_sched(slots=2, num_pages=7)
        old, young = make_req(0), make_req(1)
        s.submit(old)
        s.submit(young)
        s.admit_ready()
        young.cache_len = 12
        assert s.grow(young) is False  # young evicts itself, never the oldest
        assert old.slot is not None
        assert young.slot is None

    def test_grow_never_reserves_past_request_budget(self):
        # prompt 8 + max_new 24 = 32 tokens = 2 pages of 16; a decode window
        # larger than the remaining budget must not demand a third page
        s = ContinuousScheduler(1, {"full": PageAllocator(3, 16)}, {"full": 4}, 64)
        req = make_req(0, prompt_len=8, max_new=24)
        s.submit(req)
        s.admit_ready()
        req.cache_len = 24
        assert s.grow(req, new_tokens=16) is True  # capped at budget 32 -> 2 pages
        assert len(s.allocators["full"].owned(req.rid)) == 2

    def test_no_starvation_under_churn(self):
        """With continuous arrivals and page pressure, the oldest queued
        request is always the next admitted — arrival order is preserved."""
        s = make_sched(slots=2, num_pages=9)
        done_order = []
        for r in (make_req(0), make_req(1)):
            s.submit(r)
        rid = 2
        for step in range(200):
            s.admit_ready()
            for req in list(s.active.values()):
                req.cache_len += 1
                if req.cache_len >= len(req.prompt) + 4:
                    s.finish(req)
                    done_order.append(req.rid)
            for req in list(s.active.values()):
                s.grow(req)
            if rid < 8 and step % 3 == 0:
                s.submit(make_req(rid))
                rid += 1
            if not s.queue and not s.active:
                break
        assert done_order == sorted(done_order)  # FIFO completion, nobody starved


class TestRingRecycling:
    def test_ring_pages_capped_at_budget_under_growth(self):
        s = make_ring_sched(slots=1, num_pages=5, budget=3, page_size=4)
        req = make_req(0, prompt_len=8, max_new=40)  # 48 tokens, 12 intervals
        s.submit(req)
        s.admit_ready()
        alloc = s.allocators["ring"]
        assert len(alloc.owned(req.rid)) == 3  # replay+1 = 9 tokens -> 3 intervals
        for cache_len in range(9, 48):
            req.cache_len = cache_len
            assert s.grow(req, 1) is True
            owned = alloc.owned(req.rid)
            assert len(owned) <= 3  # never exceeds ceil(window-span/P) + 1
            assert len(req.tables["ring"]) == 3  # table stays fully linked
            assert alloc.free_pages + len(owned) == 4  # conservation

    def test_ring_admission_allocates_at_most_budget(self):
        # a long replay still only needs the ring budget, so a pool sized
        # for the window admits arbitrarily long prompts
        s = make_ring_sched(slots=1, num_pages=4, budget=3, page_size=4, max_len=256)
        req = make_req(0, prompt_len=200, max_new=8)
        s.submit(req)  # would need 52 pages append-only; ring needs 3
        assert len(s.admit_ready()) == 1
        assert len(s.allocators["ring"].owned(req.rid)) == 3

    def test_ring_recycle_interleaves_with_other_sequences(self):
        s = make_ring_sched(slots=2, num_pages=7, budget=3, page_size=4)
        a, b = make_req(0, prompt_len=4, max_new=40), make_req(1, prompt_len=4, max_new=40)
        s.submit(a)
        s.submit(b)
        assert len(s.admit_ready()) == 2
        alloc = s.allocators["ring"]
        for step in range(5, 40):
            for req in (a, b):
                req.cache_len = step
                assert s.grow(req, 1) is True
            owned_a, owned_b = set(alloc.owned(a.rid)), set(alloc.owned(b.rid))
            assert not owned_a & owned_b  # no page double-owned, ever
            assert len(owned_a) <= 3 and len(owned_b) <= 3
            assert alloc.free_pages + len(owned_a) + len(owned_b) == 6

    def test_mixed_kinds_admission_rolls_back_on_failure(self):
        # ring reservation succeeds first, then the full pool runs dry: the
        # partial ring reservation must be rolled back for the blocked head
        s = ContinuousScheduler(
            2,
            {"ring": PageAllocator(9, 4), "full": PageAllocator(5, 4)},
            {"ring": 3, "full": 8},
            32,
        )
        r0, r1 = make_req(0), make_req(1)  # replay+1 = 9 -> 3 full + 3 ring pages
        s.submit(r0)
        s.submit(r1)
        admitted = s.admit_ready()
        assert [r.rid for r in admitted] == [0]  # only 1 full page left for r1
        assert r1.tables == {} and r1.ring_hi == 0  # fully rolled back
        assert s.allocators["ring"].free_pages == 8 - 3  # only r0's pages held
        assert s.allocators["full"].free_pages == 4 - 3


class TestRhoController:
    def test_monotone_in_queue_depth(self):
        rhos = [RhoController(0.0, 0.6, 1, 16, ema=1.0).update(d) for d in range(0, 40)]
        assert all(b >= a for a, b in zip(rhos, rhos[1:]))
        assert rhos[0] == 0.0 and abs(rhos[-1] - 0.6) < 1e-9

    def test_bounded(self):
        c = RhoController(0.1, 0.5, 1, 8, ema=0.7)
        for d in (0, 3, 100, 0, 50, 2):
            rho = c.update(d)
            assert 0.1 <= rho <= 0.5

    def test_relaxes_when_drained(self):
        c = RhoController(0.0, 0.6, 1, 4, ema=0.5)
        for _ in range(10):
            high = c.update(32)
        for _ in range(20):
            low = c.update(0)
        assert high > 0.5 and low < 0.01

    def test_invalid_bounds_rejected(self):
        with pytest.raises(ValueError):
            RhoController(0.5, 0.2)


class TestContinuousEngine:
    @pytest.fixture(scope="class")
    def setup(self):
        cfg = ModelConfig(
            name="tiny-cont",
            family="dense",
            layers=2,
            d_model=64,
            heads=2,
            kv_heads=2,
            d_ff=128,
            vocab=128,
            remat="none",
        )
        params = zoo.init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, cfg.vocab, size=10).tolist() for _ in range(5)]
        return cfg, params, prompts

    def test_matches_dense_baseline(self, setup):
        cfg, params, prompts = setup
        base = ServeEngine(cfg, params, ServeConfig(slots=1, max_len=64))
        want = [base.generate([p], max_new_tokens=6)[0] for p in prompts]
        eng = ContinuousServeEngine(
            cfg, params, ContinuousServeConfig(slots=3, max_len=64, page_size=4, prefill_chunk=1)
        )
        assert eng.generate(prompts, max_new_tokens=6) == want

    def test_decode_window_matches_single_step(self, setup):
        cfg, params, prompts = setup
        one = ContinuousServeEngine(
            cfg, params, ContinuousServeConfig(slots=2, max_len=64, page_size=4, prefill_chunk=4)
        )
        want = one.generate(prompts, max_new_tokens=7)
        win = ContinuousServeEngine(
            cfg,
            params,
            ContinuousServeConfig(slots=2, max_len=64, page_size=4, prefill_chunk=4, decode_window=3),
        )
        assert win.generate(prompts, max_new_tokens=7) == want

    def test_eos_stops_early(self, setup):
        cfg, params, prompts = setup
        eng = ContinuousServeEngine(
            cfg, params, ContinuousServeConfig(slots=2, max_len=64, page_size=4, prefill_chunk=4)
        )
        full = eng.generate([prompts[0]], max_new_tokens=8)[0]
        eos = full[2]
        eng2 = ContinuousServeEngine(
            cfg, params, ContinuousServeConfig(slots=2, max_len=64, page_size=4, prefill_chunk=4)
        )
        got = eng2.generate([prompts[0]], max_new_tokens=8, eos_id=eos)[0]
        assert got[-1] == eos and len(got) <= 8

    def test_slo_and_latency_metrics(self, setup):
        cfg, params, prompts = setup
        eng = ContinuousServeEngine(
            cfg, params, ContinuousServeConfig(slots=2, max_len=64, page_size=4, prefill_chunk=4)
        )
        eng.submit(prompts[0], max_new_tokens=4, slo_s=1000.0)
        eng.submit(prompts[1], max_new_tokens=4, slo_s=1e-9)
        eng.run_until_complete()
        m = summarize(eng.requests)
        assert sum(r.done for r in eng.requests) == 2 and m["tokens"] == 8
        assert m["p50_latency_s"] > 0 and m["p99_latency_s"] >= m["p50_latency_s"]
        assert [r.slo_met() for r in eng.requests] == [True, False]
        assert all(r.ttft() is not None for r in eng.requests)

    def test_adaptive_rho_rises_under_load_and_relaxes(self, setup):
        import dataclasses

        from repro.core.dynatran import SparsityConfig

        cfg, params, prompts = setup
        cfg2 = dataclasses.replace(cfg, sparsity=SparsityConfig(mode="dynatran", target_rho=0.0))
        eng = ContinuousServeEngine(
            cfg2,
            params,
            ContinuousServeConfig(
                slots=2,
                max_len=64,
                page_size=4,
                prefill_chunk=4,
                adaptive_rho=True,
                rho_max=0.5,
                depth_lo=1,
                depth_hi=4,
            ),
        )
        for p in prompts * 2:
            eng.submit(p, max_new_tokens=4)
        peak = 0.0
        while eng.sched.queue or eng.sched.active:
            eng.step()
            peak = max(peak, eng.current_rho)
        assert peak > 0.3  # deep queue pushed rho up
        assert eng.current_rho < peak  # drained queue relaxed it
