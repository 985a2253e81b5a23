"""Serving engine: greedy generation, determinism, keyed sampling, DynaTran
runtime knob."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core.dynatran import SparsityConfig
from repro.models import zoo
from repro.serve.engine import ContinuousServeConfig, ContinuousServeEngine, ServeConfig, ServeEngine
from repro.serve.sampling import SamplingParams


def tiny_cfg(**kw):
    return ModelConfig(
        name="tiny-serve", family="dense", layers=2, d_model=64, heads=2, kv_heads=2,
        d_ff=128, vocab=128, remat="none", **kw,
    )


@pytest.fixture(scope="module")
def engine():
    cfg = tiny_cfg()
    params = zoo.init_params(jax.random.PRNGKey(0), cfg)
    return ServeEngine(cfg, params, ServeConfig(slots=4, max_len=64))


class TestServeEngine:
    def test_generate_shapes(self, engine):
        outs = engine.generate([[1, 2, 3], [4, 5]], max_new_tokens=8)
        assert len(outs) == 2
        assert all(len(o) == 8 for o in outs)
        assert all(0 <= t < 128 for o in outs for t in o)

    def test_deterministic(self, engine):
        a = engine.generate([[7, 8, 9]], max_new_tokens=6)
        b = engine.generate([[7, 8, 9]], max_new_tokens=6)
        assert a == b

    def test_eos_truncation(self, engine):
        outs = engine.generate([[1, 2]], max_new_tokens=8)
        eos = outs[0][2]
        trunc = engine.generate([[1, 2]], max_new_tokens=8, eos_id=eos)
        assert trunc[0][-1] == eos and len(trunc[0]) <= 8

    def test_greedy_matches_forward_argmax(self):
        # first generated token == argmax of forward() next-token logits
        cfg = tiny_cfg()
        params = zoo.init_params(jax.random.PRNGKey(1), cfg)
        eng = ServeEngine(cfg, params, ServeConfig(slots=2, max_len=32))
        prompt = [3, 1, 4, 1, 5]
        out = eng.generate([prompt], max_new_tokens=1)
        logits, _ = zoo.forward(params, cfg, jnp.asarray([prompt], jnp.int32))
        want = int(jnp.argmax(logits[0, -1, : cfg.vocab]))
        got = out[0][0]
        assert got == want

    def test_dynatran_runtime_knob(self):
        cfg = tiny_cfg(sparsity=SparsityConfig(mode="dynatran", target_rho=0.3))
        params = zoo.init_params(jax.random.PRNGKey(2), cfg)
        eng = ServeEngine(cfg, params, ServeConfig(slots=2, max_len=32, target_rho=0.6))
        assert eng.taus is not None
        outs = eng.generate([[1, 2, 3]], max_new_tokens=4)
        assert len(outs[0]) == 4

    def test_too_many_prompts_rejected(self, engine):
        with pytest.raises(AssertionError):
            engine.generate([[1]] * 10, max_new_tokens=1)


class TestBaselineSampling:
    """The baseline engine runs the REAL keyed sampler (shared with the
    continuous engine) instead of its old deterministic fallback."""

    def test_temperature_sampling_is_seeded_and_deterministic(self, engine):
        sp = SamplingParams(temperature=0.9, seed=11, max_new_tokens=8)
        a = engine.generate([[7, 8, 9]], sampling=sp)
        b = engine.generate([[7, 8, 9]], sampling=sp)
        assert a == b and len(a[0]) == 8
        c = engine.generate([[7, 8, 9]], sampling=dataclasses.replace(sp, seed=12))
        assert a != c  # a fresh seed re-rolls the stream

    def test_scfg_temperature_default_engages_sampler(self):
        cfg = tiny_cfg()
        params = zoo.init_params(jax.random.PRNGKey(0), cfg)
        greedy = ServeEngine(cfg, params, ServeConfig(slots=1, max_len=64))
        hot = ServeEngine(cfg, params, ServeConfig(slots=1, max_len=64, temperature=1.2))
        g = greedy.generate([[5, 6, 7]], max_new_tokens=8)
        h = hot.generate([[5, 6, 7]], max_new_tokens=8)
        assert g != h  # temperature path actually samples now

    def test_sampled_stream_matches_continuous_engine(self):
        """One sampler implementation: at the bitwise-equivalent config
        (chunk=1) both engines emit the same keyed sampled stream."""
        cfg = tiny_cfg()
        params = zoo.init_params(jax.random.PRNGKey(3), cfg)
        prompts = [[3, 1, 4, 1, 5, 9, 2, 6], [2, 7, 1, 8, 2, 8]]
        sp = SamplingParams(temperature=0.7, top_k=20, seed=5, max_new_tokens=8)
        base = ServeEngine(cfg, params, ServeConfig(slots=1, max_len=64))
        want = [base.generate([p], sampling=sp)[0] for p in prompts]
        cont = ContinuousServeEngine(
            cfg, params,
            ContinuousServeConfig(slots=1, max_len=64, page_size=4, prefill_chunk=1, prefix_caching=False),
        )
        assert [cont.generate([p], sampling=sp)[0] for p in prompts] == want

    def test_stop_set_truncates(self, engine):
        full = engine.generate([[1, 2]], max_new_tokens=8)[0]
        stops = {full[1], full[4]}
        got = engine.generate([[1, 2]], sampling=SamplingParams(stop=stops, max_new_tokens=8))[0]
        # the first emitted stop token ends generation and is kept; full[1]
        # is a stop, so the cut falls at index 1 or earlier (a stop token
        # may already appear before it)
        cut = next(j for j, t in enumerate(full) if t in stops)
        assert cut <= 1 and got == full[: cut + 1]
