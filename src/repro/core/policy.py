"""KernelPolicy: one object that says *how* kernels run.

Before this module, three loose kwargs — ``sparsity=``, ``taus=`` and
``use_pallas=`` — were threaded independently through
``models/transformer.py`` / ``models/attention.py`` / ``kernels/ops.py`` /
``serve/engine.py``.  That split the one decision AccelTran actually makes
(which datapath executes this site, and at what threshold) across call sites,
and made it easy for a backend request to be silently dropped (the old
``ops.attention`` bug).

``KernelPolicy`` folds them into a single pytree:

- **static fields** (``backend``, ``mode``, ``sites``, ``block``, ``skip``,
  ``topk_k``, ``interpret``) live in the pytree *treedef* — they are hashable
  and participate in jit's trace cache exactly like a static argument, so
  changing the backend or the tile shape recompiles, as it must;
- **runtime fields** (``taus`` — the per-site thresholds resolved from the
  DynaTran transfer curves) are pytree *leaves* — the rho knob can move every
  scheduler tick without ever triggering a retrace.

Pass a policy as a normal argument into jitted functions; nothing else is
needed.  Legacy call sites go through :func:`resolve_policy`, the single
deprecation adapter for the old kwargs.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dynatran import SITES, SparsityConfig, prune_

Array = jax.Array

__all__ = ["KernelPolicy", "derive_draft_policy", "on_tpu", "resolve_interpret", "resolve_policy"]


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU."""
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """The one place the Pallas interpret flag defaults from the platform:
    ``None`` means compiled kernels on TPU and the Pallas interpreter (a CPU
    emulation) elsewhere; an explicit bool is kept as given."""
    return (not on_tpu()) if interpret is None else bool(interpret)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class KernelPolicy:
    """How kernels execute: backend selection + dynamic-sparsity contract.

    backend:   "ref" (XLA reference ops) or "pallas" (fused kernels).
    mode:      sparsity mode — "none", "dynatran" or "topk" (mirrors
               ``SparsityConfig.mode``).
    sites:     which tensor classes are pruned at runtime (subset of
               ``dynatran.SITES``; "kv" enables scatter-time KV occupancy).
    block:     tile edge used for block-sparse skipping.
    skip:      tri-state datapath selector for the tile-granular paths.
               ``None`` (default) keeps the legacy dense datapath — pruning
               is plain ``site_prune`` masking and occupancy is ignored, so
               old numerics are reproduced bit-for-bit.  ``True`` engages
               tile skipping: dead tiles/pages are *skipped* (no gather, no
               MAC).  ``False`` runs the same tiled datapath but executes
               every tile — the exact-parity "masked" reference for the
               skipping path (identical lowering, identical bits).
    topk_k:    k for the top-k attention baseline.
    interpret: run Pallas kernels in interpret mode (CPU emulation);
               ``None`` resolves from the platform at construction
               (:func:`resolve_interpret`), so it is always a bool.
    taus:      per-site thresholds (runtime leaves; None when inactive).
    """

    backend: str = "ref"
    mode: str = "none"
    sites: tuple[str, ...] = ("ffn_act", "attn_probs", "attn_out")
    block: int = 128
    skip: bool | None = None
    topk_k: int = 64
    interpret: bool | None = None
    taus: Any = None

    def __post_init__(self):
        if self.backend not in ("ref", "pallas"):
            raise ValueError(f"unknown kernel backend {self.backend!r}")
        if self.skip not in (None, False, True):
            raise ValueError(f"skip must be None, False or True, got {self.skip!r}")
        if self.mode not in ("none", "dynatran", "topk"):
            raise ValueError(f"unknown sparsity mode {self.mode!r}")
        unknown = set(self.sites) - set(SITES)
        if unknown:
            raise ValueError(f"unknown sparsity sites {unknown}")
        self.sites = tuple(self.sites)
        self.interpret = resolve_interpret(self.interpret)

    # -- pytree protocol: taus are leaves, everything else is treedef --------
    def tree_flatten(self):
        """Pytree protocol: taus are the only leaves; every other field
        is static treedef (hashes into jit's trace cache)."""
        aux = (self.backend, self.mode, self.sites, self.block, self.skip,
               self.topk_k, self.interpret)
        return (self.taus,), aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        """Pytree protocol: rebuild from static aux + tau leaves."""
        obj = object.__new__(cls)
        (obj.backend, obj.mode, obj.sites, obj.block, obj.skip,
         obj.topk_k, obj.interpret) = aux
        (obj.taus,) = children
        return obj

    # -- constructors --------------------------------------------------------
    @classmethod
    def from_config(
        cls,
        sparsity: SparsityConfig | None,
        taus: Mapping[str, Any] | None = None,
        *,
        backend: str = "ref",
        skip: bool | None = None,
        interpret: bool | None = None,
    ) -> "KernelPolicy":
        """Lift a model-level ``SparsityConfig`` (+ resolved taus) into a policy."""
        sp = sparsity if sparsity is not None else SparsityConfig()
        return cls(
            backend=backend, mode=sp.mode, sites=tuple(sp.sites), block=sp.block,
            skip=skip, topk_k=sp.topk_k, interpret=interpret,
            taus=dict(taus) if taus is not None else None,
        )

    def with_taus(self, taus: Mapping[str, Any] | None) -> "KernelPolicy":
        """New policy with fresh runtime thresholds (no retrace: same treedef
        as long as the dict keys match)."""
        return dataclasses.replace(self, taus=dict(taus) if taus is not None else None)

    # -- queries model code asks ---------------------------------------------
    @property
    def use_pallas(self) -> bool:
        """True when the fused Pallas kernels are selected."""
        return self.backend == "pallas"

    @property
    def tiled(self) -> bool:
        """Tile-granular datapath engaged (skipping or its mask-only exact
        twin).  False for legacy/dense policies (``skip is None``)."""
        return self.skip is not None

    @property
    def active(self) -> bool:
        """Dynatran pruning is live (mode + thresholds present)."""
        return self.mode == "dynatran" and self.taus is not None

    def wants(self, site: str) -> bool:
        """Is DynaTran pruning live at this site?"""
        return self.active and site in self.sites and site in self.taus

    def tau(self, site: str):
        """The runtime threshold for ``site`` (a tensor leaf — reading it
        in a traced function never forks the jit cache)."""
        return self.taus[site]

    def prune(self, x: Array, site: str) -> Array:
        """The ``site_prune`` hook, policy-flavoured: identity unless the
        site is live, else magnitude-threshold pruning."""
        if not self.wants(site):
            return x
        return prune_(x, self.taus[site])

    @property
    def sparsity(self) -> SparsityConfig:
        """View as the model-level config (for code that still consumes one)."""
        known = tuple(s for s in self.sites if s in SITES)
        return SparsityConfig(mode=self.mode, sites=known, block=self.block,
                              topk_k=self.topk_k)


def derive_draft_policy(
    base: KernelPolicy,
    curves: Mapping[str, tuple],
    rho,
) -> KernelPolicy:
    """The draft-side policy for self-speculation: ``base`` with its taus
    re-resolved from the DynaTran transfer curves at the (typically higher)
    draft ``rho`` — AccelTran's accuracy-for-sparsity knob repurposed as a
    free draft model.

    Same treedef as ``base`` (identical static fields and tau dict keys),
    so a draft policy and the verify policy share one jit trace and moving
    ``draft_rho`` at runtime never recompiles: the taus stay runtime leaves,
    exactly like the engine's own rho controller.  ``curves`` maps site ->
    ``(rhos, taus)`` interpolation tables (the engine's host-side copies).
    When ``base`` is not in dynatran mode there is nothing to re-threshold
    and ``base`` is returned unchanged."""
    if base.mode != "dynatran" or base.taus is None:
        return base
    return base.with_taus({
        s: np.float32(np.interp(rho, *curves[s]))
        for s in base.taus
        if s in curves
    })


_SENTINEL = object()


def resolve_policy(
    policy: KernelPolicy | None = None,
    *,
    sparsity: SparsityConfig | None | object = _SENTINEL,
    taus: Mapping[str, Any] | None | object = _SENTINEL,
    use_pallas: bool | None | object = _SENTINEL,
    default_sparsity: SparsityConfig | None = None,
    interpret: bool | None = None,
) -> KernelPolicy:
    """The one deprecation adapter from the legacy kwargs to ``KernelPolicy``.

    - ``policy`` given -> returned as-is (legacy kwargs must then be unset).
    - legacy ``sparsity=`` / ``taus=`` / ``use_pallas=`` explicitly passed ->
      a ``DeprecationWarning`` and an equivalent policy (dense-datapath
      semantics: ``skip=None``, matching the old ``site_prune`` numerics
      exactly).
    - nothing given -> policy from ``default_sparsity`` (usually
      ``cfg.sparsity``), dense/ref defaults.
    """
    legacy = {
        k: v for k, v in (("sparsity", sparsity), ("taus", taus), ("use_pallas", use_pallas))
        if v is not _SENTINEL and v is not None
    }
    if policy is not None:
        if legacy:
            raise TypeError(
                f"pass either policy= or the deprecated {sorted(legacy)} kwargs, not both"
            )
        return policy
    if legacy:
        warnings.warn(
            f"the {sorted(legacy)} kwargs are deprecated; pass a KernelPolicy "
            "(see repro.core.policy.KernelPolicy.from_config)",
            DeprecationWarning,
            stacklevel=3,
        )
    sp = legacy.get("sparsity", default_sparsity)
    backend = "pallas" if legacy.get("use_pallas", False) else "ref"
    return KernelPolicy.from_config(
        sp, legacy.get("taus"), backend=backend, skip=None, interpret=interpret
    )
