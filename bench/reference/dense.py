"""Plain reference of the dense decoder family, and the seeded weights it
shares with the served model.

The forward pass is straightforward ``jax.numpy`` in float32 at
``precision=HIGHEST``: RMSNorm, optional per-head q/k RMSNorm, rotary
position embeddings (split-half rotation, theta from the configuration),
grouped-query or multi-head causal attention, a SwiGLU MLP, and a tied or
untied LM head.  It imports nothing of the program under test.  It runs
one layer per call over one sequence, so that it fits beside the weights.

``control=True`` is the same pass with every projection's inputs (weights
and activations) rounded to float8 e4m3 with one scale per tensor: the
precision one step below the bf16 the configurations state.  It exists to
show that the correctness limit rejects a lower-precision model.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
SEQ_BUCKET = 512  # sequences are padded to a multiple of this (causal: padding is never read)
ROW_BUCKET = 256  # scored positions are padded to a multiple of this
NORM_JITTER = 0.1  # norm weights are 1 + NORM_JITTER * N(0, 1)


def prng_key(seed: int):
    """A key from any whole-number seed, including ones past 32 bits."""
    return jax.random.PRNGKey(int(np.random.SeedSequence(int(seed)).generate_state(1, np.uint32)[0]))


def weight_shapes(spec, vocab_rows: int) -> dict[str, tuple[tuple[int, ...], str, float]]:
    """name -> (shape, dtype, std).  Norm weights have std 0 here and are
    drawn around 1.  ``vocab_rows`` >= ``spec.vocab`` rows of the
    embedding (and head) are held; logits are read over ``spec.vocab``."""
    L, D, H, K, hd, F = spec.layers, spec.d_model, spec.heads, spec.kv_heads, spec.head_dim, spec.d_ff
    out = {
        "embed": ((vocab_rows, D), "bfloat16", D ** -0.5),
        "final_norm": ((D,), "float32", 0.0),
        "attn_norm": ((L, D), "float32", 0.0),
        "mlp_norm": ((L, D), "float32", 0.0),
        "wq": ((L, D, H, hd), "bfloat16", D ** -0.5),
        "wk": ((L, D, K, hd), "bfloat16", D ** -0.5),
        "wv": ((L, D, K, hd), "bfloat16", D ** -0.5),
        "wo": ((L, H, hd, D), "bfloat16", (H * hd) ** -0.5),
        "w_gate": ((L, D, F), "bfloat16", D ** -0.5),
        "w_up": ((L, D, F), "bfloat16", D ** -0.5),
        "w_down": ((L, F, D), "bfloat16", F ** -0.5),
    }
    if spec.qk_norm:
        out["q_norm"] = ((L, hd), "float32", 0.0)
        out["k_norm"] = ((L, hd), "float32", 0.0)
    if not spec.tie_embeddings:
        out["lm_head"] = ((D, vocab_rows), "bfloat16", D ** -0.5)
    return out


@functools.partial(jax.jit, static_argnames=("spec", "vocab_rows"))
def _make_weights(key, spec, vocab_rows):
    out = {}
    for i, (name, (shape, dtype, std)) in enumerate(sorted(weight_shapes(spec, vocab_rows).items())):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        out[name] = (1.0 + NORM_JITTER * z if std == 0.0 else z * std).astype(dtype)
    return out


def make_weights(spec, seed: int, vocab_rows: int) -> dict:
    """Every weight on the device, in the type it is served in, from one
    jitted call: the same seed gives the same bits."""
    return _make_weights(prng_key(seed), spec, vocab_rows)


def _q8(t):
    s = 448.0 / jnp.maximum(jnp.max(jnp.abs(t)), 1e-30)
    return (t * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def _mm(x, w, control: bool):
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if control:
        x, w = _q8(x), _q8(w)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):  # x [S, H, hd]
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * freqs  # [S, hd/2]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("spec", "control"))
def _layer(w, layer, h, *, spec, control):
    S, D = h.shape
    H, K, hd = spec.heads, spec.kv_heads, spec.head_dim

    def take(name):
        return w[name][layer].astype(jnp.float32)

    x = _rms(h, take("attn_norm"), spec.norm_eps)
    q = _mm(x, take("wq").reshape(D, H * hd), control).reshape(S, H, hd)
    k = _mm(x, take("wk").reshape(D, K * hd), control).reshape(S, K, hd)
    v = _mm(x, take("wv").reshape(D, K * hd), control).reshape(S, K, hd)
    if spec.qk_norm:
        q = _rms(q, take("q_norm"), spec.norm_eps)
        k = _rms(k, take("k_norm"), spec.norm_eps)
    pos = jnp.arange(S)
    q, k = _rope(q, pos, spec.rope_theta), _rope(k, pos, spec.rope_theta)
    k, v = jnp.repeat(k, H // K, axis=1), jnp.repeat(v, H // K, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * hd ** -0.5
    scores = jnp.where(pos[None, :, None] >= pos[None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("hqk,khd->qhd", probs, v, precision=HIGHEST).reshape(S, H * hd)
    h = h + _mm(attn, take("wo").reshape(H * hd, D), control)
    x = _rms(h, take("mlp_norm"), spec.norm_eps)
    mid = jax.nn.silu(_mm(x, take("w_gate"), control)) * _mm(x, take("w_up"), control)
    return h + _mm(mid, take("w_down"), control)


@functools.partial(jax.jit, static_argnames=("spec", "control"))
def _head(w, h_rows, *, spec, control):
    x = _rms(h_rows, w["final_norm"].astype(jnp.float32), spec.norm_eps)
    head = w["embed"].T if spec.tie_embeddings else w["lm_head"]
    return _mm(x, head[:, : spec.vocab], control)


@functools.partial(jax.jit, static_argnames=("spec",))
def _embed(w, tokens, *, spec):
    return w["embed"][tokens].astype(jnp.float32)


def _pad(n: int, bucket: int) -> int:
    return -(-n // bucket) * bucket


def logits(w, spec, tokens: list[int], first: int, control: bool = False):
    """Reference logits [len(tokens) - first + 1, vocab] (f32, on device)
    predicting ``tokens[first:]`` and one token past the end: rows for
    positions ``first - 1 .. len(tokens) - 1``."""
    n_tok = len(tokens)
    seq = np.zeros((_pad(n_tok, SEQ_BUCKET),), np.int32)
    seq[:n_tok] = tokens
    rows = np.arange(first - 1, n_tok)
    picked = np.zeros((_pad(len(rows), ROW_BUCKET),), np.int32)
    picked[: len(rows)] = rows
    with jax.default_matmul_precision("highest"):
        h = _embed(w, jnp.asarray(seq), spec=spec)
        for layer in range(spec.layers):
            h = _layer(w, jnp.int32(layer), h, spec=spec, control=control)
        out = _head(w, h[jnp.asarray(picked)], spec=spec, control=control)
    return out[: len(rows)]


@jax.jit
def _gap_of(ref, chosen):
    """Per row: how far the reference's logit of ``chosen`` lies below its best."""
    return jnp.max(ref, axis=-1) - jnp.take_along_axis(ref, chosen[:, None], axis=-1)[:, 0]


def served_gap(w, spec, prompt: list[int], served: list[int]) -> float:
    """Widest gap, over the served tokens of one greedy request, between the
    reference's best logit and its logit of the served token."""
    ref = logits(w, spec, prompt + served[:-1], len(prompt))
    return float(jnp.max(_gap_of(ref, jnp.asarray(served, jnp.int32))))


def control_gap(w, spec, prompt: list[int], served: list[int]) -> tuple[float, float]:
    """(served gap, control gap) on the same prompt and tokens: the control
    gap is that of the token the float8 pass puts first at each position."""
    ref = logits(w, spec, prompt + served[:-1], len(prompt))
    ctl = logits(w, spec, prompt + served[:-1], len(prompt), control=True)
    served_g = jnp.max(_gap_of(ref, jnp.asarray(served, jnp.int32)))
    control_g = jnp.max(_gap_of(ref, jnp.argmax(ctl, axis=-1).astype(jnp.int32)))
    return float(served_g), float(control_g)
