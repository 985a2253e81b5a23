"""The serve engine's own trace marks: host spans around the phases of each
tick, named scopes inside the jitted step programs, and the work counters
``metrics()`` exports."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.models import zoo
from repro.serve.engine import ContinuousServeConfig, ContinuousServeEngine
from repro.serve.sampling import SamplingParams, sampling_tensors

SPANS = ("engine.step", "engine.admit", "engine.prepare", "engine.dispatch", "engine.wait",
         "engine.emit", "engine.copy", "engine.spill", "engine.restore")
SCOPES = ("attn", "proj", "mlp", "head", "sample")


@pytest.fixture(scope="module")
def setup():
    cfg = ModelConfig(name="tiny-trace", family="dense", layers=2, d_model=64, heads=2, kv_heads=2,
                      d_ff=128, vocab=128, remat="none")
    return cfg, zoo.init_params(jax.random.PRNGKey(0), cfg)


def make_engine(cfg, params, **kw):
    scfg = dict(slots=1, max_len=64, page_size=4, prefill_chunk=4, num_pages=10)
    scfg.update(kw)
    return ContinuousServeEngine(cfg, params, ContinuousServeConfig(**scfg))


def host_span_names(log_dir) -> set[str]:
    from jax.profiler import ProfileData

    (path,) = list(log_dir.rglob("*.xplane.pb"))
    names = set()
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(e.name for e in line.events)
    return names


def test_profiled_run_records_every_engine_span(setup, tmp_path):
    """A cached prompt sent again forks its last shared page (``copy``);
    churn under page pressure reclaims the cached chain, which spills to
    the host tier (``spill``); a later arrival with the same prefix reads
    it back (``restore``)."""
    cfg, params = setup
    rng = np.random.default_rng(3)
    system = rng.integers(1, cfg.vocab, size=8).tolist()
    prompt_a = system + rng.integers(1, cfg.vocab, size=4).tolist()
    prompt_b = system + rng.integers(1, cfg.vocab, size=4).tolist()
    churn = [rng.integers(1, cfg.vocab, size=12).tolist() for _ in range(3)]
    eng = make_engine(cfg, params)
    with jax.profiler.trace(str(tmp_path)):
        for prompt in [prompt_a, prompt_a, *churn, prompt_b]:
            eng.generate([prompt], max_new_tokens=6)
    m = eng.metrics()
    assert m["host_tier"]["prefix_spills"] > 0 and m["host_tier"]["prefix_restores"] > 0
    assert set(SPANS) <= host_span_names(tmp_path)


def test_counters_count_the_work(setup):
    cfg, params = setup
    eng = make_engine(cfg, params, slots=2, prefix_caching=True)
    rng = np.random.default_rng(5)
    system = rng.integers(1, cfg.vocab, size=8).tolist()
    first = eng.submit(system + [1, 2, 3], sampling=SamplingParams(max_new_tokens=5))
    eng.run_until_complete()
    second = eng.submit(system + [4, 5], sampling=SamplingParams(max_new_tokens=3))
    steps = 0
    while not second.done:
        eng.step()
        steps += 1
    m = eng.metrics()
    assert first.shared_tokens == 0 and second.shared_tokens == 8
    assert m["prefix_linked_tokens"] == 8
    # every prompt position is computed once, except those linked from the cache
    assert m["prefill_tokens"] == 11 + 2
    # the first token of each request comes from prefill, the rest from decode
    assert m["decode_rows"] == (5 - 1) + (3 - 1)
    assert m["decode_steps"] == m["decode_rows"]  # one live row per decode step
    assert "kv_occupancy_live" not in m


def _lowered_text(eng, which: str) -> str:
    b = eng.scfg.slots
    st = sampling_tensors(b)
    knobs = (st["temps"], st["top_ks"], st["top_ps"], st["seeds"])
    zeros = jnp.zeros((b,), jnp.int32)
    tables = eng._tables_for([])
    if which == "decode":
        lowered = eng._decode.lower(eng.params, eng.pools, eng.slot_state, eng.occupancy, tables, zeros,
                                    jnp.zeros((b, 1), jnp.int32), jnp.zeros((b,), bool), eng.policy,
                                    *knobs, jnp.asarray(st["steps"]), sample=True)
    else:
        lowered = eng._prefill.lower(eng.params, eng.pools, eng.slot_state, eng.occupancy, tables, zeros,
                                     jnp.zeros((b, eng.scfg.prefill_chunk), jnp.int32), zeros,
                                     jnp.zeros((b,), bool), eng.policy, *knobs, sample=True)
    return lowered.as_text(debug_info=True)


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_step_programs_carry_every_scope(setup, which):
    cfg, params = setup
    text = _lowered_text(make_engine(cfg, params, slots=2), which)
    parts = {p for loc in re.findall(r'loc\("([^"]*)"', text) for p in loc.split("/")}
    assert set(SCOPES) <= parts
