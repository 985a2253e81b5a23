"""KernelPolicy contract: taus are runtime pytree leaves (a rho change never
retraces), static fields participate in the jit cache, ``resolve_policy`` is
the single deprecation adapter (legacy kwargs warn; policy + legacy is an
error), and the migrated entry points accept a policy without warning."""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dynatran import SparsityConfig
from repro.core.policy import KernelPolicy, resolve_policy


def dynatran_sp(sites=("ffn_act", "attn_probs", "attn_out")):
    return SparsityConfig(mode="dynatran", sites=sites)


class TestPytree:
    def test_taus_are_runtime_leaves_no_retrace(self):
        traces = 0

        @jax.jit
        def f(x, pol):
            nonlocal traces
            traces += 1
            return pol.prune(x, "ffn_act")

        x = jnp.asarray([0.1, 0.5, -0.7], jnp.float32)
        p1 = KernelPolicy.from_config(dynatran_sp(), {"ffn_act": 0.3})
        o1 = f(x, p1)
        o2 = f(x, p1.with_taus({"ffn_act": 0.6}))  # the runtime rho knob
        assert traces == 1, "changing taus must reuse the jit cache entry"
        xn = np.asarray(x)
        np.testing.assert_array_equal(np.asarray(o1), np.where(np.abs(xn) >= 0.3, xn, 0.0))
        np.testing.assert_array_equal(np.asarray(o2), np.where(np.abs(xn) >= 0.6, xn, 0.0))

    def test_static_field_change_retraces(self):
        traces = 0

        @jax.jit
        def f(x, pol):
            nonlocal traces
            traces += 1
            return x * (2.0 if pol.tiled else 1.0)

        x = jnp.ones((3,))
        pol = KernelPolicy.from_config(dynatran_sp(), {"ffn_act": 0.1})
        f(x, pol)
        f(x, dataclasses.replace(pol, skip=True))  # static: must recompile
        assert traces == 2

    def test_flatten_roundtrip(self):
        pol = KernelPolicy.from_config(
            dynatran_sp(("ffn_act", "kv")), {"ffn_act": 0.1, "kv": 0.2},
            backend="pallas", skip=True, interpret=False,
        )
        leaves, treedef = jax.tree_util.tree_flatten(pol)
        back = jax.tree_util.tree_unflatten(treedef, leaves)
        assert back.backend == "pallas" and back.skip is True
        assert back.sites == ("ffn_act", "kv") and back.interpret is False
        assert set(back.taus) == {"ffn_act", "kv"}

    def test_tri_state_skip(self):
        assert KernelPolicy(skip=None).tiled is False
        assert KernelPolicy(skip=False).tiled is True
        assert KernelPolicy(skip=True).tiled is True
        with pytest.raises(ValueError):
            KernelPolicy(skip="yes")

    def test_validation(self):
        with pytest.raises(ValueError):
            KernelPolicy(backend="cuda")
        with pytest.raises(ValueError):
            KernelPolicy(mode="static")
        with pytest.raises(ValueError):
            KernelPolicy(sites=("ffn_act", "nope"))


class TestInterpretDefault:
    def test_default_follows_platform(self):
        """``interpret=None`` resolves at construction: the interpreter off
        the TPU, compiled kernels on it; an explicit flag is kept."""
        assert KernelPolicy().interpret is (jax.default_backend() != "tpu")
        assert KernelPolicy(interpret=False).interpret is False
        assert dataclasses.replace(KernelPolicy(), interpret=True).interpret is True


class TestQueries:
    def test_wants_needs_mode_site_and_tau(self):
        sp = dynatran_sp(("ffn_act", "kv"))
        pol = KernelPolicy.from_config(sp, {"ffn_act": 0.1})
        assert pol.wants("ffn_act")
        assert not pol.wants("kv")  # in sites but no tau resolved
        assert not pol.wants("attn_out")  # tau-less AND not a site
        assert pol.with_taus({"ffn_act": 0.1, "kv": 0.5}).wants("kv")
        assert not KernelPolicy.from_config(SparsityConfig(), {"ffn_act": 0.1}).wants("ffn_act")

    def test_prune_identity_when_inactive(self):
        x = jnp.asarray([0.01, -0.02])
        pol = KernelPolicy.from_config(SparsityConfig())  # mode "none"
        assert pol.prune(x, "ffn_act") is x

    def test_sparsity_view_roundtrip(self):
        sp = dynatran_sp(("ffn_act", "attn_out"))
        view = KernelPolicy.from_config(sp).sparsity
        assert view.mode == sp.mode and view.sites == sp.sites and view.block == sp.block


class TestResolveAdapter:
    def test_policy_passthrough_no_warning(self):
        pol = KernelPolicy.from_config(dynatran_sp(), {"ffn_act": 0.1})
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            assert resolve_policy(pol) is pol

    def test_legacy_kwargs_warn_and_map(self):
        sp = dynatran_sp()
        with pytest.warns(DeprecationWarning, match="deprecated"):
            pol = resolve_policy(sparsity=sp, taus={"ffn_act": 0.2}, use_pallas=True)
        assert pol.mode == "dynatran" and pol.use_pallas
        assert pol.skip is None, "legacy callers must get the dense datapath"
        assert float(pol.tau("ffn_act")) == pytest.approx(0.2)

    def test_policy_plus_legacy_is_an_error(self):
        pol = KernelPolicy()
        with pytest.raises(TypeError, match="not both"):
            resolve_policy(pol, taus={"ffn_act": 0.1})

    def test_default_sparsity_silent(self):
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            pol = resolve_policy(default_sparsity=dynatran_sp())
        assert pol.mode == "dynatran" and pol.taus is None and not pol.active

    def test_explicit_none_legacy_kwargs_are_silent(self):
        # the common internal pattern: f(..., taus=None) forwarding defaults
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            resolve_policy(None, sparsity=None, taus=None, use_pallas=None)


class TestDeprecatedEntryPoints:
    """The old kwargs still work at the public entry points — through the one
    adapter, with a DeprecationWarning — and a policy kwarg never warns."""

    def _qkv(self):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        return tuple(jax.random.normal(k, (1, 8, 2, 16), jnp.float32) for k in ks)

    def test_reference_attention_legacy_warns(self):
        from repro.models.attention import reference_attention

        q, k, v = self._qkv()
        sp = dynatran_sp(("attn_probs",))
        with pytest.warns(DeprecationWarning):
            old = reference_attention(q, k, v, causal=True, sparsity=sp, taus={"attn_probs": 0.1})
        new = reference_attention(
            q, k, v, causal=True, policy=KernelPolicy.from_config(sp, {"attn_probs": 0.1})
        )
        np.testing.assert_array_equal(np.asarray(old), np.asarray(new))

    def test_moe_ffn_legacy_warns(self):
        from repro.models.moe import moe_ffn, moe_init

        p = moe_init(jax.random.PRNGKey(0), 16, 2, 32, glu=False)
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 4, 16), jnp.float32)
        sp = dynatran_sp(("ffn_act",))
        with pytest.warns(DeprecationWarning):
            old, _ = moe_ffn(x=x, params=p, n_experts=2, top_k=1, glu=False,
                             sparsity=sp, taus={"ffn_act": 0.1})
        new, _ = moe_ffn(x=x, params=p, n_experts=2, top_k=1, glu=False,
                         policy=KernelPolicy.from_config(sp, {"ffn_act": 0.1}))
        np.testing.assert_array_equal(np.asarray(old), np.asarray(new))

    def test_ops_attention_backend_is_honest(self):
        """The old dispatch silently fell back to the reference kernel even
        when Pallas was requested; a pallas-backend policy must now route to
        the fused kernel (whose online-softmax reassociation is visible as a
        small-but-nonzero difference from the materialised reference)."""
        from repro.kernels import ops

        q, k, v = self._qkv()
        ref_out = ops.attention(q, k, v, policy=KernelPolicy(backend="ref"))
        pal_out = ops.attention(q, k, v, policy=KernelPolicy(backend="pallas"))
        np.testing.assert_allclose(np.asarray(ref_out), np.asarray(pal_out), rtol=2e-5, atol=2e-5)
        assert np.asarray(ref_out).dtype == np.asarray(pal_out).dtype
