"""The correctness check can fail: at smoke size on the CPU, the float8
control put in the program's place comes out ``correct: false``, reading
over the limit that the program's gaps on the same sample read under, and
a run with the timed path broken underneath comes out ``correct: false``
for each fault a served cell can have."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from bench.run import run_cell

CELLS = ("qwen3-smoke.chat", "deepseek-smoke.docqa")


@pytest.mark.parametrize("cell", CELLS)
def test_control_reads_over_the_limit_that_sound_runs_read_under(smoke_root, cell):
    for seed in (101, 2**31 + 102):
        r = run_cell(smoke_root, cell, seed, 1.0, False, require_chip=False, control=True)
        check = r["checks"]["max_logit_gap"]
        assert r["correct"] is False
        assert check["value"] == max(r["control_gaps"]) > check["limit"], r["control_gaps"]
        assert max(r["gaps"]) <= check["limit"]


def _token_altered(engine):
    """Every decoded token is replaced by the next id where it is produced."""
    decode = engine._decode

    def altered(*args, **kw):
        pools, ssm, occ, toks = decode(*args, **kw)
        return pools, ssm, occ, (toks + 1) % engine.cfg.vocab

    engine._decode = altered


def _state_unchanged(engine):
    """The decode step returns the cache it was given: no decoded token's
    K/V is ever written."""
    decode = jax.jit(engine._decode_impl, static_argnames=("sample",))

    def unchanged(params, pools, ssm, occ, *rest, **kw):
        return (pools, ssm, occ) + (decode(params, pools, ssm, occ, *rest, **kw)[3],)

    engine._decode = unchanged


def _half_batch(engine):
    """The upper half of the batch's rows is left out of the decode step:
    their tokens come out as id 0."""
    decode = engine._decode
    half = engine.scfg.slots // 2

    def halved(*args, **kw):
        pools, ssm, occ, toks = decode(*args, **kw)
        return pools, ssm, occ, jnp.where(jnp.arange(toks.shape[1])[None, :] < half, toks, 0)

    engine._decode = halved


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged, _half_batch], ids=lambda f: f.__name__[1:])
def test_a_broken_timed_path_is_not_correct(smoke_root, cell, fault):
    r = run_cell(smoke_root, cell, 303, 1.0, False, require_chip=False, engine_hook=fault)
    assert r["correct"] is False
    assert r["checks"]["max_logit_gap"]["value"] > r["checks"]["max_logit_gap"]["limit"]
