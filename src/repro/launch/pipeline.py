"""GPipe-style pipeline parallelism over the "pod" mesh axis.

The production default for 2 pods is pure data-parallel over "pod" (one
cross-pod gradient all-reduce per step, DCN-friendly).  This module is the
alternative: split the layer stack into ``n_stages`` contiguous stages, one
per pod, and stream microbatches through with `collective_permute` handoffs
— demonstrating that the framework's multi-pod story is not tied to DP.

Implementation: `shard_map` over the "pod" axis.  Each device along "pod"
holds its stage's parameter slice (the stacked-blocks leading axis is
sharded over "pod").  The classic GPipe rotation runs n_micro + n_stages - 1
ticks; at each tick a stage applies its blocks to its resident microbatch
and passes activations to the next stage with `jax.lax.ppermute`.

Used by the dry-run (--pipeline) to prove the collective-permute schedule
lowers and by tests on a host mesh.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.policy import KernelPolicy
from repro.models import transformer, zoo

Array = jax.Array


def stage_fn(cfg: ModelConfig, blocks: Any, h: Array, positions: Array) -> Array:
    """Apply this stage's share of the layer stack (stacked leading axis)."""

    def body(hh, cycle_params):
        for i, pat in enumerate(cfg.attention_pattern):
            hh, _ = transformer.block_apply(
                cycle_params[str(i)], cfg, pat, hh, positions, None,
                KernelPolicy.from_config(cfg.sparsity),
            )
        return hh, ()

    h, _ = jax.lax.scan(body, h, blocks)
    return h


def make_pipeline_forward(cfg: ModelConfig, mesh: Mesh, n_micro: int, axis: str = "pod"):
    """Builds fn(params, tokens [B, S]) -> final hidden states, with the
    layer stack split over the ``axis`` mesh dimension (GPipe schedule)."""
    n_stages = mesh.shape[axis]
    assert cfg.n_cycles % n_stages == 0, (cfg.n_cycles, n_stages)

    def fwd(params, tokens):
        B, S = tokens.shape
        assert B % n_micro == 0
        positions = jnp.arange(S)

        def per_stage(blocks, h_embedded):
            # h_embedded: this stage's slice of the microbatch queue
            # [n_micro/b_stage? no: every stage sees all microbatches in turn]
            stage = jax.lax.axis_index(axis)
            n_ticks = n_micro + n_stages - 1
            mb = h_embedded.reshape(n_micro, B // n_micro, S, cfg.d_model)

            def tick(carry, t):
                buf, outputs = carry  # buf: the activation resident on this stage
                # stage 0 injects microbatch t (if any left); others use buf
                inject = mb[jnp.minimum(t, n_micro - 1)]
                x = jnp.where(stage == 0, inject, buf)
                y = stage_fn(cfg, blocks, x, positions)
                # pass to the next stage (ring; last stage's output collected)
                nxt = jax.lax.ppermute(y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
                done_idx = t - (n_stages - 1)
                outputs = jax.lax.cond(
                    (done_idx >= 0) & (stage == n_stages - 1),
                    lambda o: o.at[jnp.maximum(done_idx, 0)].set(y),
                    lambda o: o,
                    outputs,
                )
                return (nxt, outputs), ()

            outputs = jnp.zeros_like(mb)
            (buf, outputs), _ = jax.lax.scan(
                tick, (jnp.zeros_like(mb[0]), outputs), jnp.arange(n_ticks)
            )
            # broadcast the last stage's collected outputs to every stage
            # (mask + psum: a one-to-all ppermute needs duplicate sources)
            outputs = jax.lax.psum(
                jnp.where(stage == n_stages - 1, outputs, jnp.zeros_like(outputs)), axis
            )
            return outputs.reshape(B, S, cfg.d_model)

        h = params["embed"][tokens]
        if cfg.embed_scale:
            h = h * jnp.sqrt(float(cfg.d_model)).astype(h.dtype)

        shard = functools.partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=(P(axis), P()),
            out_specs=P(),
            check_vma=False,
        )
        out = shard(per_stage)(params["blocks"], h)
        _, norm = transformer.make_norm(cfg.norm)
        out = norm(params["final_norm"], out)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        return out @ head.astype(out.dtype)

    return fwd


def pipeline_param_shardings(cfg: ModelConfig, abstract_params, mesh: Mesh, axis: str = "pod"):
    """Blocks' stacked leading axis over ``axis`` (stage-major); everything
    else replicated (composable with TP/FSDP on the remaining axes via the
    standard rules if desired)."""

    def one(path_entries, leaf):
        keys = [str(getattr(p, "key", getattr(p, "idx", getattr(p, "name", p)))) for p in path_entries]
        if "blocks" in keys and leaf.ndim >= 1 and leaf.shape[0] == cfg.n_cycles:
            return NamedSharding(mesh, P(axis))
        return NamedSharding(mesh, P())

    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract_params)
    return jax.tree_util.tree_unflatten(treedef, [one(p, l) for p, l in flat])
