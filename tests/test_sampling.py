"""Keyed vectorized sampler: greedy exactness, filter support, per-row
(seed, step) determinism, batch-composition independence, and equality
with the argsort + gather formulation it replaced."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serve.sampling import NEG_INF, SamplingParams, _row_keys, sample_tokens
from repro.serve.scheduler import Request


def _sample(logits, temps, top_ks, top_ps, seeds, steps):
    return np.asarray(
        sample_tokens(
            jnp.asarray(logits, jnp.float32),
            jnp.asarray(temps, jnp.float32),
            jnp.asarray(top_ks, jnp.int32),
            jnp.asarray(top_ps, jnp.float32),
            jnp.asarray(seeds, jnp.uint32),
            jnp.asarray(steps, jnp.int32),
        )
    )


@pytest.fixture(scope="module")
def logits():
    rng = np.random.default_rng(0)
    return rng.normal(size=(4, 64)).astype(np.float32)


class TestSampleTokens:
    def test_temperature_zero_is_exact_argmax(self, logits):
        got = _sample(logits, [0.0] * 4, [0] * 4, [1.0] * 4, [0] * 4, [0] * 4)
        assert (got == logits.argmax(-1)).all()

    def test_top_k_one_is_argmax_even_when_hot(self, logits):
        got = _sample(logits, [2.0] * 4, [1] * 4, [1.0] * 4, [1, 2, 3, 4], [0] * 4)
        assert (got == logits.argmax(-1)).all()

    def test_tiny_top_p_is_argmax(self, logits):
        got = _sample(logits, [1.5] * 4, [0] * 4, [1e-6] * 4, [5, 6, 7, 8], [3] * 4)
        assert (got == logits.argmax(-1)).all()

    def test_top_k_support(self, logits):
        """Sampled ids always come from each row's top-k set."""
        k = 5
        topk = np.argsort(-logits, axis=-1)[:, :k]
        for step in range(40):
            got = _sample(logits, [1.3] * 4, [k] * 4, [1.0] * 4, [9] * 4, [step] * 4)
            for b in range(4):
                assert got[b] in topk[b]

    def test_deterministic_in_seed_and_step(self, logits):
        a = _sample(logits, [0.9] * 4, [0] * 4, [1.0] * 4, [3] * 4, [7] * 4)
        b = _sample(logits, [0.9] * 4, [0] * 4, [1.0] * 4, [3] * 4, [7] * 4)
        assert (a == b).all()
        c = _sample(logits, [0.9] * 4, [0] * 4, [1.0] * 4, [3] * 4, [8] * 4)
        d = _sample(logits, [0.9] * 4, [0] * 4, [1.0] * 4, [4] * 4, [7] * 4)
        # a fresh key re-rolls every row with overwhelming probability
        assert (a != c).any() and (a != d).any()

    def test_batch_composition_independence(self, logits):
        """A row's sample depends only on (its logits, seed, step) — not on
        which other rows share the batch (the eviction-replay and
        cross-engine determinism contract)."""
        full = _sample(logits, [0.8] * 4, [10] * 4, [0.9] * 4, [11, 12, 13, 14], [2, 5, 9, 0])
        for b in range(4):
            solo = _sample(logits[b : b + 1], [0.8], [10], [0.9], [11 + b], [[2, 5, 9, 0][b]])
            assert solo[0] == full[b]

    def test_mixed_greedy_and_sampled_rows(self, logits):
        got = _sample(logits, [0.0, 1.2, 0.0, 1.2], [0] * 4, [1.0] * 4, [1] * 4, [4] * 4)
        assert got[0] == logits[0].argmax() and got[2] == logits[2].argmax()

    def test_sampled_distribution_tracks_logits(self):
        """With a strongly peaked distribution, the mode dominates."""
        v = 16
        logits = np.full((1, v), -4.0, np.float32)
        logits[0, 3] = 4.0
        hits = sum(
            int(_sample(logits, [1.0], [0], [1.0], [0], [s])[0] == 3) for s in range(100)
        )
        assert hits > 90


class TestSamplingParams:
    def test_validation(self):
        with pytest.raises(ValueError):
            SamplingParams(top_k=-1)
        with pytest.raises(ValueError):
            SamplingParams(top_p=0.0)
        with pytest.raises(ValueError):
            SamplingParams(max_new_tokens=0)

    def test_stop_set_coercion_and_with_stop(self):
        sp = SamplingParams(stop=[3, 5, 3])
        assert sp.stop == frozenset({3, 5})
        assert sp.with_stop(9).stop == frozenset({3, 5, 9})

    def test_request_eos_alias_builds_stop_set(self):
        r = Request(rid=0, prompt=[1], max_new_tokens=4, eos_id=7)
        assert r.stop_ids == frozenset({7}) and r.params.max_new_tokens == 4

    def test_request_params_win_and_absorb_eos(self):
        sp = SamplingParams(temperature=0.5, stop=[2], max_new_tokens=9)
        r = Request(rid=0, prompt=[1], max_new_tokens=99, eos_id=7, params=sp)
        assert r.stop_ids == frozenset({2, 7})
        assert r.max_new_tokens == 9  # params govern; field is a mirror

    def test_negative_eos_ignored(self):
        r = Request(rid=0, prompt=[1], max_new_tokens=4)
        assert r.stop_ids == frozenset()


def test_row_keys_match_scalar_fold_in():
    """The vmapped per-row key derivation equals the scalar reference, so a
    request's stream is reproducible from (seed, step) alone."""
    seeds = jnp.asarray([0, 1, 2], jnp.uint32)
    steps = jnp.asarray([5, 5, 7], jnp.int32)
    keys = jax.vmap(lambda s, t: jax.random.fold_in(jax.random.PRNGKey(s), t))(seeds, steps)
    want = jax.random.fold_in(jax.random.PRNGKey(np.uint32(1)), 5)
    assert np.array_equal(np.asarray(keys[1]), np.asarray(want))


def _reference_sample(logits, temps, top_ks, top_ps, seeds, steps):
    """The sampler as first written: sorted logits by argsort + a gather,
    the k-th largest by a gather; the same filters and Gumbel keys."""
    v = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    order = jnp.argsort(-scaled, axis=-1)
    sorted_l = jnp.take_along_axis(scaled, order, axis=-1)
    k = jnp.clip(jnp.where(top_ks > 0, top_ks, v), 1, v)
    kth = jnp.take_along_axis(sorted_l, (k - 1)[:, None], axis=-1)
    masked = jnp.where(scaled >= kth, scaled, NEG_INF)
    sorted_m = jnp.where(sorted_l >= kth, sorted_l, NEG_INF)
    probs = jax.nn.softmax(sorted_m, axis=-1)
    cum_excl = jnp.cumsum(probs, axis=-1) - probs
    min_kept = jnp.min(jnp.where(cum_excl < top_ps[:, None], sorted_m, jnp.inf), axis=-1)
    masked = jnp.where(masked >= min_kept[:, None], masked, NEG_INF)
    gumbel = jax.vmap(lambda key: jax.random.gumbel(key, (v,), jnp.float32))(_row_keys(seeds, steps))
    sampled = jnp.argmax(masked + gumbel, axis=-1).astype(jnp.int32)
    return jnp.where(temps > 0, sampled, greedy)


def _boundary_ties(rng, b, v):
    """Coarse logits (many exact ties), with the k-th and (k+1)-th largest
    of each row made equal for the row's k, and a run of equal values
    where the nucleus boundary falls."""
    logits = np.round(rng.normal(size=(b, v)) * 2.0) / 2.0
    top_ks = rng.choice([1, 3, 7, v // 2], size=b)
    for r in range(b):
        order = np.argsort(-logits[r], kind="stable")
        logits[r, order[top_ks[r]]] = logits[r, order[top_ks[r] - 1]]
        logits[r, order[2:9]] = logits[r, order[2]]
    temps = rng.choice([0.0, 0.7, 1.0, 1.3], size=b)
    top_ps = rng.choice([0.3, 0.5, 0.8, 0.95], size=b)
    return logits, temps, top_ks, top_ps


def _mixed_filters(rng, b, v):
    """Greedy and sampled rows with every filter setting crossed."""
    logits = rng.normal(size=(b, v)) * rng.uniform(0.5, 4.0, size=(b, 1))
    temps = np.where(np.arange(b) % 4 == 0, 0.0, rng.choice([0.5, 0.8, 1.7], size=b))
    top_ks = np.array([0, 1, 7, v])[np.arange(b) % 4]
    top_ps = np.array([1e-6, 0.5, 0.95, 1.0])[(np.arange(b) // 4) % 4]
    return logits, temps, top_ks, top_ps


def _signed_zeros(rng, b, v):
    """Logits of exactly -0.0 and +0.0 tied at the top and at the k-th
    place: the sort ties them, so their order must not matter."""
    logits = np.where(rng.random((b, v)) < 0.5, -0.0, 0.0) - (rng.random((b, v)) < 0.5) * rng.random((b, v))
    temps = rng.choice([0.0, 1.0], size=b)
    top_ks = rng.choice([0, 2, 5], size=b)
    top_ps = rng.choice([0.1, 0.9, 1.0], size=b)
    return logits, temps, top_ks, top_ps


def _full_vocab(rng, b, v):
    logits = rng.normal(size=(b, v)) * 3.0
    return logits, np.array([0.8, 1.0]), np.array([0, 40]), np.array([0.95, 0.5])


@pytest.mark.parametrize(
    "make, b, v",
    [(_boundary_ties, 8, 64), (_boundary_ties, 8, 1000), (_mixed_filters, 16, 64),
     (_mixed_filters, 16, 1000), (_signed_zeros, 8, 64), (_full_vocab, 2, 151936)],
    ids=["ties-64", "ties-1000", "mixed-64", "mixed-1000", "signed-zeros", "vocab-151936"],
)
def test_same_tokens_as_argsort_gather_sampler(make, b, v):
    """The value-only sort gives the sorted values the argsort + gather gave,
    so every token is the same: greedy and sampled rows alike, across seeds
    and steps."""
    rng = np.random.default_rng(v + b)
    logits, temps, top_ks, top_ps = make(rng, b, v)
    args = (jnp.asarray(logits, jnp.float32), jnp.asarray(temps, jnp.float32),
            jnp.asarray(top_ks, jnp.int32), jnp.asarray(top_ps, jnp.float32))
    ref = jax.jit(_reference_sample)
    for step in range(4):
        seeds = jnp.asarray(rng.integers(0, 2**32, size=b, dtype=np.uint64).astype(np.uint32))
        steps = jnp.full((b,), step, jnp.int32)
        want = np.asarray(ref(*args, seeds, steps))
        got = np.asarray(sample_tokens(*args, seeds, steps))
        assert np.array_equal(got, want), (step, got, want)


def _gathers_of(jaxpr, shape):
    """``gather`` equations whose operand has ``shape``, in ``jaxpr`` and
    every jaxpr nested in its equations' parameters."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather" and tuple(eqn.invars[0].aval.shape) == shape:
            found.append(eqn)
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    found += _gathers_of(inner, shape)
    return found


def test_no_full_vocabulary_gather():
    """No gather over ``[B, V]`` in the sampler at the real vocabulary: on a
    TPU such a gather runs element by element.  The reference has one, so
    the walk does reach into nested jaxprs."""
    b, v = 4, 151936
    args = (jax.ShapeDtypeStruct((b, v), jnp.float32),) + tuple(
        jax.ShapeDtypeStruct((b,), d) for d in (jnp.float32, jnp.int32, jnp.float32, jnp.uint32, jnp.int32)
    )
    assert _gathers_of(jax.make_jaxpr(_reference_sample)(*args).jaxpr, (b, v))
    assert not _gathers_of(jax.make_jaxpr(sample_tokens)(*args).jaxpr, (b, v))
