"""Per-request sampling: ``SamplingParams`` + a vectorized keyed sampler.

Both serving engines share one sampler.  All knobs enter the jitted decode
step as *runtime per-row tensors* (same no-recompile discipline as the
DynaTran taus): changing a request's temperature, top-k, top-p, or seed
never retraces, and a batch can mix greedy and sampled rows freely.

Determinism contract: the token sampled for a request depends only on
``(logits, seed, step)`` where ``step`` is the request's generated-token
index.  It does NOT depend on batch composition, engine slot, or decode
scheduling — so eviction + replay reproduces a sampled request bit-exactly
(replayed tokens are fed back, never re-sampled), and the continuous and
baseline engines emit identical streams for identical logits.

Rows with ``temperature <= 0`` take the exact argmax path the engines have
always used, so greedy serving stays bitwise-identical to the dense-KV
reference.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

NEG_INF = jnp.float32(-1e30)


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request decode policy, carried on each ``Request``.

    ``temperature <= 0`` means greedy (argmax); ``top_k == 0`` and
    ``top_p >= 1`` disable their filters.  ``stop`` is a *set* of stop
    token ids — generation ends when any of them is emitted (the stop
    token is included in the output, matching the old ``eos_id``
    behaviour).  ``max_new_tokens`` caps the generated length.
    """

    temperature: float = 0.0
    top_k: int = 0  # 0 = disabled (full vocab)
    top_p: float = 1.0  # 1.0 = disabled
    seed: int = 0
    stop: frozenset[int] = frozenset()
    max_new_tokens: int = 32

    def __post_init__(self):
        if self.top_k < 0:
            raise ValueError("top_k must be >= 0 (0 disables the filter)")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError("need 0 < top_p <= 1")
        if self.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        # accept any iterable of ints for ergonomics; store a frozenset
        object.__setattr__(self, "stop", frozenset(int(t) for t in self.stop))

    def with_stop(self, *token_ids: int) -> "SamplingParams":
        """A copy with ``token_ids`` added to the stop set."""
        return dataclasses.replace(self, stop=self.stop | set(token_ids))


def sampling_tensors(rows: int) -> dict[str, np.ndarray]:
    """Host-side default tensors for one batch (all rows greedy)."""
    return {
        "temps": np.zeros((rows,), np.float32),
        "top_ks": np.zeros((rows,), np.int32),
        "top_ps": np.ones((rows,), np.float32),
        "seeds": np.zeros((rows,), np.uint32),
        "steps": np.zeros((rows,), np.int32),
    }


def fill_row(t: dict[str, np.ndarray], row: int, params: SamplingParams, step: int) -> None:
    t["temps"][row] = params.temperature
    t["top_ks"][row] = params.top_k
    t["top_ps"][row] = params.top_p
    t["seeds"][row] = np.uint32(params.seed & 0xFFFFFFFF)
    t["steps"][row] = step


def _row_keys(seeds: Array, steps: Array) -> Array:
    """One PRNG key per row from (seed, step): independent of batch
    composition and slot placement."""
    return jax.vmap(lambda s, t: jax.random.fold_in(jax.random.PRNGKey(s), t))(seeds, steps)


def sample_tokens(
    logits: Array,  # [B, V] float32 (vocab already sliced)
    temps: Array,  # [B] float32; <= 0 -> greedy row
    top_ks: Array,  # [B] int32; 0 -> disabled
    top_ps: Array,  # [B] float32; 1.0 -> disabled
    seeds: Array,  # [B] uint32
    steps: Array,  # [B] int32: generated-token index being sampled
) -> Array:
    """Vectorized temperature / top-k / top-p sampling with per-row keys.

    Filters compose the standard way: logits are divided by temperature,
    everything outside the top-k is masked, then the smallest nucleus with
    cumulative probability >= top_p is kept (ties at the boundary are kept,
    so the nucleus never loses probability mass to ordering).  Sampling is
    the Gumbel-argmax trick over the masked logits.  Greedy rows
    (``temps <= 0``) return exactly ``argmax(logits)``.
    """
    v = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    # descending values by a value-only sort: every filter below needs the
    # sorted values, never their indices, and a per-element gather along the
    # vocabulary is slow on a TPU.  Unstable, so it stays one operand (a
    # stable sort carries an iota): the comparator only ties -0.0 with +0.0
    # (and NaNs), which no comparison or sum below tells apart.
    sorted_l = -jax.lax.sort(-scaled, dimension=1, is_stable=False)

    # top-k: keep logits >= the k-th largest (runtime per-row k), picked by
    # a select-and-reduce rather than a gather
    k = jnp.where(top_ks > 0, top_ks, v)
    k = jnp.clip(k, 1, v)
    at_k = jax.lax.broadcasted_iota(jnp.int32, sorted_l.shape, 1) == (k - 1)[:, None]
    kth = jnp.max(jnp.where(at_k, sorted_l, -jnp.inf), axis=-1, keepdims=True)  # [B, 1]
    masked = jnp.where(scaled >= kth, scaled, NEG_INF)

    # top-p over the top-k-filtered distribution: keep the tokens whose
    # EXCLUSIVE cumulative probability (in descending order) is < top_p —
    # the smallest prefix reaching top_p, boundary token included
    sorted_m = jnp.where(sorted_l >= kth, sorted_l, NEG_INF)
    probs = jax.nn.softmax(sorted_m, axis=-1)
    cum_excl = jnp.cumsum(probs, axis=-1) - probs
    keep_sorted = cum_excl < top_ps[:, None]
    min_kept = jnp.min(jnp.where(keep_sorted, sorted_m, jnp.inf), axis=-1)  # [B]
    masked = jnp.where(masked >= min_kept[:, None], masked, NEG_INF)

    gumbel = jax.vmap(lambda key: jax.random.gumbel(key, (v,), jnp.float32))(_row_keys(seeds, steps))
    sampled = jnp.argmax(masked + gumbel, axis=-1).astype(jnp.int32)
    return jnp.where(temps > 0, sampled, greedy)


def accept_matched(draft_tokens: Array, target_tokens: Array) -> Array:
    """Speculative acceptance count: the longest prefix of ``draft_tokens``
    [k, B] that token-for-token equals ``target_tokens`` [k, B] — returns
    ``m`` [B] int32 with 0 <= m <= k.

    Exact token identity is the correct rule for BOTH greedy and sampled
    rows here, because the engine couples the streams path-wise rather than
    distribution-wise: draft step i and verify step i sample with the SAME
    per-row key (``fold_in(PRNGKey(seed), step)`` at the same generated-token
    index), and the engine always emits the TARGET's samples.  The emitted
    stream is therefore unconditionally the non-speculative stream, bit for
    bit; drafts only decide how many of those target tokens a tick may emit
    (a draft that predicted the target's token validates the next verify
    position's inputs).  Classic rejection-resampling would accept tokens
    the target's own keyed stream would not have produced, breaking the
    repo's replay-determinism contract — equality never does."""
    match = (draft_tokens == target_tokens).astype(jnp.int32)
    return jnp.sum(jnp.cumprod(match, axis=0), axis=0).astype(jnp.int32)
