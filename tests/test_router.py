"""Multi-replica router: affinity parity vs a single engine, lossless
health failover, rho-before-shed ordering, per-tenant fairness and
throttling, engine drain/adopt handoff, metrics memoization, and the
queue-conservation churn property (hypothesis when available, plus a
deterministic anchor)."""
import time

import pytest

from repro.router import Router, RouterPolicy
from repro.router.metrics import render_prometheus
from repro.router.policy import FairQueue, TokenBucket
from repro.serve.sampling import SamplingParams
from repro.serve.scheduler import Request

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # [test] extra installs it in CI; degrade to the anchor
    HAVE_HYPOTHESIS = False

PAGE = 4


# ---------------------------------------------------------------------------
# FakeEngine: the minimal replica protocol (adopt/drain/cancel/step/load/
# metrics [+ prefix_cache, set_target_rho]) — policy tests run in
# microseconds and stay deterministic
# ---------------------------------------------------------------------------


class FakeEngine:
    def __init__(self, slots: int = 2, rho_knob: bool = True):
        self.slots = slots
        self.reqs: list[Request] = []
        self.rho = 0.0
        self.rho_calls: list[float] = []
        self.rho_knob = rho_knob
        self.prefix_cache = None
        self.steps = 0

    def adopt(self, req: Request) -> Request:
        req._engine = self
        self.reqs.append(req)
        return req

    def drain(self) -> list[Request]:
        out = [r for r in self.reqs if not r.done and not r.cancelled]
        for r in out:
            r.evictions += 1
            r.ready = False
            r.prefill_pos = 0
            r.cache_len = 0
        self.reqs = []
        return out

    def cancel(self, req: Request) -> None:
        if req.done:
            return
        req.cancelled = True
        req.finish_time = time.perf_counter()
        if req in self.reqs:
            self.reqs.remove(req)

    @property
    def load(self) -> int:
        return len(self.reqs)

    def set_target_rho(self, rho: float) -> None:
        if not self.rho_knob:
            raise ValueError("no rho knob on this replica")
        self.rho = rho
        self.rho_calls.append(rho)

    def step(self) -> list[Request]:
        self.steps += 1
        done = []
        for r in list(self.reqs[: self.slots]):
            r.generated.append(7)
            if len(r.generated) >= r.max_new_tokens:
                r.finish_time = time.perf_counter()
                done.append(r)
                self.reqs.remove(r)
        return done

    def metrics(self) -> dict:
        return {
            "total_tokens": sum(len(r.generated) for r in self.reqs),
            "total_requests": 0,
            "queue_depth": self.load,
            "rho": self.rho,
        }


def conserved(router: Router) -> bool:
    return (
        router.submitted
        == router.completed + router.sheds + router.cancelled
        + router.backlog + router.in_flight
    )


# ---------------------------------------------------------------------------
# policy primitives
# ---------------------------------------------------------------------------


class TestTokenBucket:
    def test_burst_then_refill(self):
        now = [0.0]
        b = TokenBucket(rate=10.0, burst=20.0, clock=lambda: now[0])
        assert b.take(20.0)
        assert not b.take(1.0)
        now[0] = 1.0  # +10 tokens
        assert b.take(10.0)
        assert not b.take(0.5)

    def test_burst_caps_refill(self):
        now = [0.0]
        b = TokenBucket(rate=100.0, burst=5.0, clock=lambda: now[0])
        now[0] = 100.0
        assert b.peek(5.0) and not b.peek(5.1)


class TestFairQueue:
    def _req(self, rid, tenant, n=4):
        return Request(rid=rid, prompt=[1, 2], tenant=tenant,
                       params=SamplingParams(max_new_tokens=n))

    def test_weighted_interleave(self):
        fq = FairQueue(rate=float("inf"), burst=float("inf"),
                       weights={"heavy": 1.0, "light": 1.0})
        for i in range(6):
            fq.push(self._req(i, "heavy"))
        for i in range(6, 9):
            fq.push(self._req(i, "light"))
        order = [fq.pop().tenant for _ in range(9)]
        # equal weights + equal cost: once light joins, strict alternation
        assert order.count("light") == 3
        assert "light" in order[:2], f"light starved at head: {order}"
        first_six = order[:6]
        assert first_six.count("light") >= 2, f"no interleave: {order}"

    def test_idle_tenant_banks_no_credit(self):
        fq = FairQueue(rate=float("inf"), burst=float("inf"))
        for i in range(4):
            fq.push(self._req(i, "busy"))
        for _ in range(4):
            fq.pop()
        # late joiner starts at the live minimum vt, not at 0
        fq.push(self._req(10, "busy"))
        fq.push(self._req(11, "late"))
        late = fq.tenants["late"]
        busy = fq.tenants["busy"]
        assert late.vt >= busy.vt - 1e-9

    def test_throttled_tenant_defers_not_drops(self):
        now = [0.0]
        fq = FairQueue(rate=1.0, burst=6.0, clock=lambda: now[0])
        fq.push(self._req(0, "a", n=4))  # cost 2 + 4 = 6: drains the bucket
        fq.push(self._req(1, "a", n=4))
        assert fq.pop() is not None
        assert fq.pop() is None  # throttled, still queued
        assert fq.tenants["a"].throttles == 1
        assert fq.depth == 1
        now[0] = 6.0
        assert fq.pop() is not None  # refill released it


# ---------------------------------------------------------------------------
# router over stub replicas: ladder ordering, fairness, failover, conservation
# ---------------------------------------------------------------------------


class TestShedLadder:
    def test_rho_climbs_before_first_shed(self):
        engines = [FakeEngine(slots=1)]
        router = Router(engines, RouterPolicy(
            replica_depth_hw=1, queue_cap=6, depth_lo=2, depth_hi=6, rho_ema=0.7,
            rho_levels=(0.0, 0.25, 0.5, 0.7),
        ))
        shed_seen = False
        for i in range(80):
            r = router.submit([1, 2, 3], max_new_tokens=4)
            if r.shed and not shed_seen:
                shed_seen = True
                # structural ordering: a shed is only legal once the ladder
                # saturated, and every intermediate rung was announced first
                assert router.ladder.saturated
                assert [rho for _, rho in router.rho_trace] == [0.0, 0.25, 0.5, 0.7]
            router.step()
        assert shed_seen, "flood never shed"
        assert router.first_shed_tick is not None
        sat_tick = next(t for t, rho in router.rho_trace if rho >= 0.7)
        assert sat_tick <= router.first_shed_tick
        # the replicas were actually retargeted, in ladder order
        assert engines[0].rho_calls == [0.0, 0.25, 0.5, 0.7]
        assert conserved(router)

    def test_no_shed_below_queue_cap(self):
        router = Router([FakeEngine(slots=1)], RouterPolicy(
            replica_depth_hw=1, queue_cap=10_000, depth_lo=1, depth_hi=4, rho_ema=1.0,
        ))
        for _ in range(50):
            assert not router.submit([1, 2], max_new_tokens=2).shed
            router.step()
        assert router.sheds == 0  # saturated rho alone never sheds

    def test_rho_knobless_fleet_collapses_ladder(self):
        engines = [FakeEngine(slots=1, rho_knob=False)]
        router = Router(engines, RouterPolicy(
            replica_depth_hw=1, queue_cap=4, depth_lo=1, depth_hi=4,
        ))
        assert not router._can_degrade
        assert router.ladder.levels == [0.0]  # nothing to trade: backlog-only shed
        for _ in range(20):
            router.submit([1, 2], max_new_tokens=4)
            router.step()
        assert router.sheds > 0
        assert engines[0].rho_calls == []
        assert conserved(router)


class TestFairnessUnderFlood:
    def test_adversarial_flood_backlogs_only_itself(self):
        router = Router([FakeEngine(slots=1)], RouterPolicy(replica_depth_hw=1))
        flood = [router.submit([1, 2], tenant="flood", max_new_tokens=1) for _ in range(12)]
        fair = [router.submit([1, 2], tenant="fair", max_new_tokens=1) for _ in range(3)]
        done_order = []
        for _ in range(60):
            done_order += router.step()
            if all(r.done for r in flood + fair):
                break
        order = [r.tenant for r in done_order]
        # weighted fairness: the light tenant finishes all 3 while the flood
        # still holds most of its backlog
        last_fair = max(i for i, t in enumerate(order) if t == "fair")
        flood_done_by_then = order[: last_fair + 1].count("flood")
        assert flood_done_by_then <= 6, f"flood starved the light tenant: {order}"
        assert conserved(router)

    def test_tenant_throttle_counts_and_releases(self):
        now = [0.0]
        router = Router(
            [FakeEngine(slots=4)],
            RouterPolicy(replica_depth_hw=8, tenant_rate=1.0, tenant_burst=6.0),
            clock=lambda: now[0],
        )
        a = router.submit([1, 2], tenant="a", max_new_tokens=4)  # cost 6
        b = router.submit([1, 2], tenant="a", max_new_tokens=4)  # over budget
        for _ in range(8):
            router.step()
        assert a.done and not b.done  # b deferred, never dropped
        m = router.metrics()
        assert m["throttles"] == 1
        assert m["tenant_depth"]["a"] == 1
        now[0] = 6.0  # refill
        router.run_until_complete()
        assert b.done and not b.shed
        assert conserved(router)


class TestFailoverStubs:
    def test_kill_requeues_and_completes_elsewhere(self):
        e0, e1 = FakeEngine(slots=2), FakeEngine(slots=2)
        router = Router([e0, e1], RouterPolicy(replica_depth_hw=4))
        reqs = [router.submit([1, 2, 3], max_new_tokens=6) for _ in range(4)]
        for _ in range(2):
            router.step()
        victim = 0 if e0.load else 1
        router.health.kill(victim)
        router.run_until_complete()
        assert all(r.done and not r.cancelled for r in reqs)
        assert router.health.failovers == 1
        assert router.metrics()["failovers"] == 1
        assert conserved(router)

    def test_revive_readmits(self):
        e0, e1 = FakeEngine(slots=1), FakeEngine(slots=1)
        router = Router([e0, e1], RouterPolicy(replica_depth_hw=2))
        router.health.kill(0)
        router.submit([1, 2], max_new_tokens=2)
        router.step()
        assert e0.load == 0  # dead replica got nothing
        router.health.revive(0)
        reqs = [router.submit([1, 2], max_new_tokens=2) for _ in range(4)]
        router.run_until_complete()
        assert all(r.done for r in reqs)
        assert e0.steps > 0  # back in rotation


class TestChurnAnchor:
    """Deterministic churn: submit/step/cancel/kill/revive interleaved, the
    conservation invariant holding after every op (the hypothesis property
    below explores the same space randomly when available)."""

    def test_fixed_churn_conserves(self):
        e = [FakeEngine(slots=1), FakeEngine(slots=1)]
        router = Router(e, RouterPolicy(replica_depth_hw=2, queue_cap=5,
                                        depth_lo=1, depth_hi=4, rho_ema=1.0))
        live: list[Request] = []
        script = (["submit"] * 6 + ["step", "cancel", "kill0", "step", "submit",
                  "step", "revive0", "cancel"] + ["submit"] * 6 + ["step"] * 4
                  + ["cancel", "kill1", "step", "step", "revive1"] + ["step"] * 30)
        for op in script:
            if op == "submit":
                live.append(router.submit([1, 2, 3], max_new_tokens=3))
            elif op == "step":
                router.step()
            elif op == "cancel":
                victim = next((r for r in live if not r.done), None)
                if victim is not None:
                    victim.cancel()  # the handle routes through the router
            elif op.startswith("kill"):
                router.health.kill(int(op[-1]))
            elif op.startswith("revive"):
                router.health.revive(int(op[-1]))
            assert conserved(router), f"after {op}"
        router.run_until_complete()
        assert conserved(router)
        assert router.backlog == 0 and router.in_flight == 0
        assert all(r.done for r in live)


if HAVE_HYPOTHESIS:
    ops = st.lists(
        st.one_of(
            st.tuples(st.just("submit"), st.sampled_from(["a", "b", "c"]),
                      st.integers(1, 4)),
            st.tuples(st.just("step"), st.just(0), st.just(0)),
            st.tuples(st.just("cancel"), st.integers(0, 30), st.just(0)),
            st.tuples(st.just("kill"), st.integers(0, 1), st.just(0)),
            st.tuples(st.just("revive"), st.integers(0, 1), st.just(0)),
        ),
        min_size=1, max_size=60,
    )

    class TestChurnProperty:
        @given(ops=ops)
        @settings(max_examples=60, deadline=None)
        def test_queue_conservation_under_churn(self, ops):
            router = Router(
                [FakeEngine(slots=1), FakeEngine(slots=1)],
                RouterPolicy(replica_depth_hw=2, queue_cap=4,
                             depth_lo=1, depth_hi=3, rho_ema=1.0),
            )
            live: list[Request] = []
            for op, x, y in ops:
                if op == "submit":
                    live.append(router.submit([1, 2], tenant=x, max_new_tokens=y))
                elif op == "step":
                    router.step()
                elif op == "cancel" and x < len(live):
                    if not live[x].done:
                        router.cancel(live[x])
                elif op == "kill":
                    router.health.kill(x)
                elif op == "revive":
                    router.health.revive(x)
                assert conserved(router)
            for i in range(2):
                router.health.revive(i)
            router.run_until_complete()
            assert conserved(router)
            assert router.backlog == 0 and router.in_flight == 0
else:

    @pytest.mark.skip(reason="property churn needs hypothesis ([test] extra)")
    def test_queue_conservation_under_churn():
        pass


# ---------------------------------------------------------------------------
# real engines: parity, affinity, lossless failover, drain/adopt, metrics
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def setup():
    import jax
    import numpy as np

    from repro.configs.base import ModelConfig
    from repro.models import zoo

    cfg = ModelConfig(
        name="tiny-router", family="dense", layers=2, d_model=64, heads=2,
        kv_heads=2, d_ff=128, vocab=128, remat="none",
    )
    params = zoo.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    sys_prompt = rng.integers(1, cfg.vocab, size=2 * PAGE).tolist()  # 2 full pages
    prompts = [sys_prompt + rng.integers(1, cfg.vocab, size=3).tolist() for _ in range(6)]
    return cfg, params, prompts, sys_prompt


def make_engine(cfg, params, **kw):
    from repro.serve.engine import ContinuousServeConfig, ContinuousServeEngine

    defaults = dict(slots=2, max_len=64, page_size=PAGE, prefill_chunk=4)
    defaults.update(kw)
    return ContinuousServeEngine(cfg, params, ContinuousServeConfig(**defaults))


class TestRealEngines:
    def test_affinity_routing_matches_single_engine(self, setup):
        cfg, params, prompts, _ = setup
        ref = make_engine(cfg, params).generate(prompts, max_new_tokens=8)
        router = Router(
            [make_engine(cfg, params), make_engine(cfg, params)],
            RouterPolicy(replica_depth_hw=4),
        )
        got = router.generate(prompts, max_new_tokens=8)
        assert got == ref  # greedy rows are independent of placement
        m = router.metrics()
        assert m["completed"] == len(prompts) and m["sheds"] == 0
        assert m["total_tokens"] == sum(len(g) for g in got)

    def test_affinity_prefers_warm_replica(self, setup):
        cfg, params, _, sys_prompt = setup
        router = Router(
            [make_engine(cfg, params), make_engine(cfg, params)],
            RouterPolicy(replica_depth_hw=4),
        )
        wave1 = [router.submit(sys_prompt + [20 + i], max_new_tokens=4) for i in range(2)]
        router.run_until_complete()
        assert router.affinity_hits == 0  # cold fleet: everything least-loaded
        wave2 = [router.submit(sys_prompt + [40 + i], max_new_tokens=4) for i in range(4)]
        router.run_until_complete()
        assert all(r.done for r in wave1 + wave2)
        assert router.affinity_hits == 4  # warm prefix pages attract wave 2
        assert router.metrics()["affinity_hit_rate"] > 0

    def test_health_kill_mid_decode_replays_losslessly(self, setup):
        cfg, params, prompts, _ = setup
        two = prompts[:2]
        ref = make_engine(cfg, params).generate(two, max_new_tokens=10)
        router = Router(
            [make_engine(cfg, params), make_engine(cfg, params)],
            RouterPolicy(replica_depth_hw=2),
        )
        reqs = [router.submit(p, max_new_tokens=10) for p in two]
        for _ in range(6):  # both mid-decode
            router.step()
        assert any(r.generated for r in reqs)
        victim = next(i for i, h in enumerate(router.replicas) if h.inflight)
        router.health.kill(victim)
        router.run_until_complete()
        assert [r.generated for r in reqs] == ref  # replay, not re-sample
        assert router.health.failovers == 1
        assert sum(r.evictions for r in reqs) >= 1

    def test_engine_drain_adopt_handoff(self, setup):
        cfg, params, prompts, _ = setup
        two = prompts[:2]
        ref = make_engine(cfg, params).generate(two, max_new_tokens=8)
        src, dst = make_engine(cfg, params), make_engine(cfg, params)
        reqs = [src.submit(p, max_new_tokens=8) for p in two]
        for _ in range(5):
            src.step()
        moved = src.drain()
        assert {r.rid for r in moved} == {r.rid for r in reqs if not r.done}
        assert src.load == 0
        for r in moved:
            dst.adopt(r)
        # rid guard: a fresh submit on dst must not collide with adopted rids
        extra = dst.submit(two[0], max_new_tokens=2)
        assert extra.rid > max(r.rid for r in moved)
        dst.run_until_complete()
        assert [r.generated for r in reqs] == ref
        src.run_until_complete()  # drained engine finishes whatever stayed

    def test_metrics_memoized_and_monotonic(self, setup):
        cfg, params, prompts, _ = setup
        eng = make_engine(cfg, params)
        reqs = [eng.submit(p, max_new_tokens=4) for p in prompts[:2]]
        eng.run_until_complete()
        m1 = eng.metrics()
        assert m1 is eng.metrics()  # memoized: no state change, same object
        assert m1["total_tokens"] == sum(len(r.generated) for r in reqs)
        assert m1["total_requests"] == 2 and all(r.done for r in reqs)
        # shedding is a router decision; the engine never sheds and must not
        # report a vestigial always-zero counter (it shadowed the real one)
        assert "sheds" not in m1
        eng.clear_history()
        m2 = eng.metrics()
        assert m2 is not m1  # trim invalidates the memo...
        assert m2["total_tokens"] == m1["total_tokens"]  # ...counters survive it
        eng.submit(prompts[0], max_new_tokens=2)
        eng.run_until_complete()
        m3 = eng.metrics()
        assert m3["total_requests"] == 3
        assert m3["total_tokens"] == m1["total_tokens"] + 2

    def test_prometheus_rendering(self, setup):
        cfg, params, prompts, _ = setup
        router = Router([make_engine(cfg, params)], RouterPolicy(replica_depth_hw=4))
        router.generate(prompts[:2], max_new_tokens=4)
        text = render_prometheus(router.metrics())
        assert "repro_router_requests_completed_total 2" in text
        assert 'repro_router_replica_queue_depth{replica="0"} 0' in text
        assert text.count("# TYPE repro_router_replica_tokens_total counter") == 1
        # host-tier families render for every replica, zeros when idle
        assert 'repro_router_replica_tier_restores_total{replica="0"} 0' in text
        assert "# TYPE repro_router_replica_tier_bytes_used gauge" in text
        assert 'repro_router_replica_tier_restore_ratio{replica="0"} 0.0' in text


class TestRhoEpoch:
    def _dynatran_engine(self, setup, **kw):
        import dataclasses

        from repro.core.dynatran import SparsityConfig

        cfg, params, _, _ = setup
        cfg = dataclasses.replace(
            cfg, sparsity=SparsityConfig(mode="dynatran", target_rho=0.0)
        )
        return cfg, params

    def test_retarget_bumps_epoch_and_drops_cache(self, setup):
        cfg, params = self._dynatran_engine(setup)
        _, _, prompts, _ = setup
        eng = make_engine(cfg, params)
        eng.generate(prompts[:2], max_new_tokens=2)
        assert eng.prefix_cache.cached_pages > 0
        epoch = eng._rho_epoch
        eng.set_target_rho(0.5)
        assert eng._rho_epoch == epoch + 1
        assert eng.prefix_cache.cached_pages == 0  # old-taus pages gone
        eng.set_target_rho(0.5)  # idempotent: same rho, same epoch
        assert eng._rho_epoch == epoch + 1

    def test_adaptive_engine_rejects_fleet_knob(self, setup):
        cfg, params = self._dynatran_engine(setup)
        eng = make_engine(cfg, params, adaptive_rho=True)
        with pytest.raises(ValueError, match="adaptive"):
            eng.set_target_rho(0.3)

    def test_sparsity_off_rejects_fleet_knob(self, setup):
        cfg, params, _, _ = setup
        eng = make_engine(cfg, params)
        with pytest.raises(ValueError, match="rho knob"):
            eng.set_target_rho(0.3)
