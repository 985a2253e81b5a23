"""Ahead-of-time compiles for one TPU v5e chip, described and not attached.

The TPU compiler is installed next to JAX, so a kernel or a whole step can be
compiled for a described ``v5e:2x2`` topology on a CPU-only host: Mosaic
refuses what the chip would refuse (unaligned slices, vector loads from HBM,
too much VMEM), and ``memory_analysis()`` gives the compiler's own memory
view.  Nothing runs, so these tests say nothing about results or times.

The topology is described inside a module fixture — never at import — so
only the worker that runs this file loads the TPU library.  The persistent
compilation cache is switched off around the compiles (an entry written for
a described chip cannot be read back without one).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.core.policy import KernelPolicy
from repro.kernels.paged_attention import paged_decode_attention, paged_gather
from repro.models import transformer as tfm
from repro.models import zoo

V5E_HBM_BYTES = 16e9
SLOTS, MAX_LEN, PAGE = 8, 2048, 16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    # libtpu ships with the pinned JAX: a failure to describe the chip is a
    # broken installation and fails these tests, it never skips them
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def on_chip(tree, sharding):
    """Abstract values of ``tree`` placed on the described chip."""
    return jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree)


def kernel_case(variant: str, n_pages: int | None = None):
    """(cfg, static kwargs, array kwargs, table span) of one decode-kernel
    variant at a published model's widths; the pools hold ``n_pages`` pages
    (default: 8 slots of the span, as the engine sizes them)."""
    if variant == "ring":
        cfg = configs.get_config("gemma2-9b")  # Hkv 8, head_dim 256, window 4096
        return cfg, {"window": cfg.window, "logit_cap": cfg.attn_logit_cap}, {}, cfg.window
    cfg = configs.get_config("qwen3-4b")
    n_pages = n_pages or SLOTS * MAX_LEN // PAGE + 1
    if variant == "int8":
        scale = jax.ShapeDtypeStruct((n_pages, PAGE, cfg.kv_heads), jnp.bfloat16)
        return cfg, {}, {"k_scale": scale, "v_scale": scale}, MAX_LEN
    if variant == "occupancy":
        occ = jax.ShapeDtypeStruct((n_pages, PAGE), jnp.bool_)
        return cfg, {"skip": True, "with_visits": True}, {"occupancy": occ}, MAX_LEN
    return cfg, {}, {}, MAX_LEN


def compile_kernel(one_chip, variant: str, n_pages: int | None = None, tp: int = 1):
    """``paged_decode_attention`` compiled for one chip, with the heads of
    one of ``tp`` KV-head shards (as the TP engine calls it per device).
    The int8 scales keep all KV heads, so int8 takes ``tp=1`` only."""
    cfg, static, arrays, span = kernel_case(variant, n_pages)
    maxp = span // PAGE
    n_pages = n_pages or SLOTS * maxp + 1
    pool_dtype = jnp.int8 if variant == "int8" else jnp.bfloat16
    pool = jax.ShapeDtypeStruct((n_pages, PAGE, cfg.kv_heads // tp, cfg.hd), pool_dtype)
    args = (
        jax.ShapeDtypeStruct((SLOTS, 1, cfg.heads // tp, cfg.hd), jnp.bfloat16),
        pool, pool,
        jax.ShapeDtypeStruct((SLOTS, maxp), jnp.int32),
        jax.ShapeDtypeStruct((SLOTS,), jnp.int32),
    )

    def run(q, k, v, table, lengths, arrays):
        return paged_decode_attention(q, k, v, table, lengths, interpret=False, **arrays, **static)

    return jax.jit(run).lower(*on_chip(args, one_chip), on_chip(arrays, one_chip)).compile()


def full_hbm_pages(variant: str, tp: int) -> int:
    """Pages of qwen3-4b's pool that fill one v5e's HBM beside the
    replicated weights, with the pool split over ``tp`` KV-head shards."""
    cfg = configs.get_config("qwen3-4b")
    param_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(zoo.abstract_params(cfg)))
    per_layer = 2 * PAGE * (cfg.kv_heads // tp) * cfg.hd  # K and V elements of one page
    if variant == "int8":
        page_bytes = cfg.layers * (per_layer + 2 * PAGE * (cfg.kv_heads // tp) * 2)  # + bf16 scales
    else:
        page_bytes = cfg.layers * per_layer * 2
    return int((V5E_HBM_BYTES - param_bytes) // page_bytes)


@pytest.mark.parametrize("variant", ["full", "int8", "ring", "occupancy"])
def test_paged_decode_attention_compiles(one_chip, variant):
    assert "tpu_custom_call" in compile_kernel(one_chip, variant).as_text()


@pytest.mark.parametrize("variant,tp", [("int8", 1), ("occupancy", 1), ("occupancy", 4)])
def test_side_tables_fit_vmem_at_a_full_pool(one_chip, variant, tp):
    """The int8 scales and the occupancy bits are held whole in VMEM, so
    VMEM grows with the pool: compile at the largest pool one v5e holds,
    and at one device's shard of a TP=4 engine (4x the pages, 2 KV heads).
    (int8 at 2 KV heads per device is refused by Mosaic whatever the pool:
    its page DMA needs 4 heads, the int8 sublane tiling.)"""
    n_pages = full_hbm_pages(variant, tp)
    assert n_pages > SLOTS * MAX_LEN // PAGE
    assert "tpu_custom_call" in compile_kernel(one_chip, variant, n_pages, tp).as_text()


def test_paged_gather_compiles(one_chip):
    cfg = configs.get_config("qwen3-4b")
    maxp = MAX_LEN // PAGE
    args = (
        jax.ShapeDtypeStruct((SLOTS * maxp + 1, PAGE, cfg.kv_heads, cfg.hd), jnp.bfloat16),
        jax.ShapeDtypeStruct((SLOTS, maxp), jnp.int32),
    )
    compiled = jax.jit(lambda pool, table: paged_gather(pool, table, interpret=False)).lower(
        *on_chip(args, one_chip)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def compile_decode_step(one_chip, policy: KernelPolicy):
    """qwen3-4b's paged decode step at full width (8 slots, max_len 2048,
    page 16), pools donated as the engine donates them."""
    cfg = configs.get_config("qwen3-4b")
    layout = tfm.paged_layout(cfg, MAX_LEN, PAGE)
    num_pages = {k: SLOTS * layout.budget(k) + 1 for k in layout.kinds}
    pools = jax.eval_shape(lambda: tfm.init_paged_state(cfg, layout, num_pages))
    tables = {k: jax.ShapeDtypeStruct((SLOTS, layout.budget(k)), jnp.int32) for k in layout.kinds}
    args = (
        zoo.abstract_params(cfg), pools, tables,
        jax.ShapeDtypeStruct((SLOTS,), jnp.int32),
        jax.ShapeDtypeStruct((SLOTS, 1), jnp.int32),
    )

    def step(params, pools, tables, length, tokens):
        logits, pools, _, _ = tfm.paged_decode_step(params, cfg, layout, pools, tables, length, tokens, policy=policy)
        return logits, pools

    return jax.jit(step, donate_argnums=(1,)).lower(*on_chip(args, one_chip)).compile()


def test_ref_decode_step_fits_one_chip(one_chip):
    mem = compile_decode_step(one_chip, KernelPolicy(backend="ref")).memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM_BYTES


def test_pallas_decode_step_holds_the_kernel(one_chip):
    policy = KernelPolicy(backend="pallas", interpret=False)
    compiled = compile_decode_step(one_chip, policy)
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM_BYTES

