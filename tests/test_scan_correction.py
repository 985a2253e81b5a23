"""Validate the dry-run's scan-body cost correction: XLA's cost analysis
visits a while-loop body once, so the corrected FLOPs of a scanned model must
match the cost analysis of the same model with the loop unrolled."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest


def _flops(fn, *args):
    c = jax.jit(fn).lower(*args).compile()
    return float((c.cost_analysis() or {}).get("flops", 0.0))


class TestScanBodyCounting:
    def test_while_body_counted_once(self):
        """The premise: cost_analysis is trip-count-blind for lax.scan."""
        w = jnp.ones((64, 64))
        x = jnp.ones((8, 64))

        def scanned(n):
            def f(x, w):
                def body(c, _):
                    return jnp.tanh(c @ w), ()

                c, _ = jax.lax.scan(body, x, None, length=n)
                return c

            return f

        f2 = _flops(scanned(2), x, w)
        f8 = _flops(scanned(8), x, w)
        # body visited once regardless of length (if this ever changes, the
        # dry-run correction must be retired — this test is the canary)
        assert f2 == pytest.approx(f8, rel=0.01)

    def test_correction_matches_unrolled(self):
        """F_true = F(raw) + (trips-1) * F_body with F_body = F(raw) - F_head
        must agree with the unrolled compile."""
        w = jnp.ones((64, 64))
        x = jnp.ones((8, 64))
        trips = 6

        def head(x):
            return (x * 2.0).sum()  # negligible-FLOP head

        def scanned(x, w):
            def body(c, _):
                return jnp.tanh(c @ w), ()

            c, _ = jax.lax.scan(body, x, None, length=trips)
            return head(c)

        def unrolled(x, w):
            c = x
            for _ in range(trips):
                c = jnp.tanh(c @ w)
            return head(c)

        raw = _flops(scanned, x, w)
        full = _flops(unrolled, x, w)
        head_flops = 2 * x.size  # mul + sum
        body = max(raw - head_flops, 0.0)
        corrected = raw + (trips - 1) * body
        assert corrected == pytest.approx(full, rel=0.05), (corrected, full)

    def test_model_level_correction(self):
        """End-to-end: a 1-cycle vs 4-cycle smoke transformer — corrected
        4-cycle FLOPs must be ~4x the per-layer cost."""
        from repro.configs import get_smoke
        from repro.models import zoo

        cfg4 = dataclasses.replace(get_smoke("qwen3-4b"), remat="none")
        assert cfg4.n_cycles >= 2
        cfg1 = dataclasses.replace(cfg4, layers=cfg4.pattern_len)  # one cycle
        tokens = jnp.ones((2, 32), jnp.int32)

        params4 = zoo.init_params(jax.random.PRNGKey(0), cfg4)
        params1 = jax.tree_util.tree_map(
            lambda x: x[:1] if x.ndim > 0 and x.shape[0] == cfg4.n_cycles else x,
            params4,
        )
        # align: params under "blocks" have the leading cycle axis
        f4 = _flops(lambda p, t: zoo.forward(p, cfg4, t)[0].sum(), params4, tokens)
        f1 = _flops(lambda p, t: zoo.forward(p, cfg1, t)[0].sum(), params1, tokens)
        # body counted once in both -> raw flops nearly equal
        assert f4 == pytest.approx(f1, rel=0.05)
