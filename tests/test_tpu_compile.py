"""Compile the main-path Pallas kernels for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler lowers each kernel at real
widths (phi4-mini-3.8b attention: 24 q / 8 kv heads, head_dim 128; its MLP
for the matmul) for a ``v5e:2x2`` topology that is described, not attached.
The compiler refuses what interpret mode accepts — a block that breaks the
(8, 128) tiling rule, a kernel that needs more scoped VMEM than it gets —
so these tests guard the chip path at no chip time.  Every call passes
``interpret=False`` explicitly: on the CPU backend the plans resolve to
interpret mode, which compiles no kernel at all.

The topology is described inside a fixture (never at import: only one
process may load the TPU library, and test workers import every file).
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.matmul import matmul
from repro.kernels.paged_attention import paged_attention
from repro.tune import plan_for

BATCH, MAX_LEN, HQ, HKV, D = 8, 2048, 24, 8, 128
# internlm2-20b's shard of heads under TP=4 (48 q / 8 kv heads over 4 chips)
TP4_HQ, TP4_HKV = 12, 2


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile is written to the persistent cache but can
    # never be read back without a chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _paged_shapes(kv_dtype, window=None):
    plan = plan_for("paged_attention", shape_sig=(MAX_LEN, D),
                    dtype=jnp.dtype(kv_dtype).name)
    page = plan.page_size
    slots = (-(-window // page) + 1) if window else -(-MAX_LEN // page)
    pool = 1 + BATCH * slots
    return plan, page, slots, pool


@pytest.mark.parametrize("variant", ["bf16", "window_softcap", "int8",
                                     "tp4_bf16"])
def test_paged_attention_compiles(one_chip, variant):
    kv = jnp.int8 if variant == "int8" else jnp.bfloat16
    window = 512 if variant == "window_softcap" else None
    hq, hkv = (TP4_HQ, TP4_HKV) if variant == "tp4_bf16" else (HQ, HKV)
    plan, page, slots, pool = _paged_shapes(kv, window)
    shapes = [((BATCH, hq, D), jnp.bfloat16),
              ((pool, page, hkv, D), kv), ((pool, page, hkv, D), kv),
              ((BATCH, slots), jnp.int32), ((BATCH,), jnp.int32)]
    if variant == "int8":
        shapes += [((pool, page), jnp.float32)] * 2

        def fn(q, kp, vp, t, vl, ks, vs):
            return paged_attention(q, kp, vp, t, vl, k_scale=ks, v_scale=vs,
                                   plan=plan, interpret=False)
    else:
        fn = functools.partial(
            paged_attention, plan=plan, interpret=False, window=window,
            softcap=50.0 if window else None)
    _compile(fn, one_chip, *shapes)


@pytest.mark.parametrize("wrapped", [True, False], ids=["jit", "unwrapped"])
def test_paged_attention_keeps_its_name(one_chip, wrapped):
    """In a caller's program of another name, with or without the kernel's
    own ``jax.jit`` around it, the kernel's custom call is
    ``paged_attention.N``: the name a trace reduction finds it by."""
    plan, page, slots, pool = _paged_shapes(jnp.bfloat16)
    kernel = paged_attention if wrapped else paged_attention.__wrapped__

    def decode_window(q, kp, vp, t, vl):
        return kernel(q, kp, vp, t, vl, plan=plan, interpret=False)

    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in (
        ((BATCH, HQ, D), jnp.bfloat16), ((pool, page, HKV, D), jnp.bfloat16),
        ((pool, page, HKV, D), jnp.bfloat16), ((BATCH, slots), jnp.int32),
        ((BATCH,), jnp.int32))]
    text = jax.jit(decode_window).lower(*args).compile().as_text()
    calls = re.findall(r"%(\S+) = .*custom_call_target=\"tpu_custom_call\"",
                       text)
    assert calls
    assert all(re.fullmatch(r"paged_attention(\.\d+)*", c) for c in calls)


def test_paged_attention_reads_the_pool_in_place(one_chip):
    """At phi4-mini's decode shapes the compiled call holds no copy,
    transpose or fusion of a pool-sized array: the kernel reads each page
    from the pool as it lies (the per-head relayout, bf16[16392,8,128] a
    call for K and for V, is gone)."""
    plan, page, slots, pool = _paged_shapes(jnp.bfloat16)

    def decode_window(q, kp, vp, t, vl):
        return paged_attention(q, kp, vp, t, vl, plan=plan, interpret=False)

    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in (
        ((BATCH, HQ, D), jnp.bfloat16), ((pool, page, HKV, D), jnp.bfloat16),
        ((pool, page, HKV, D), jnp.bfloat16), ((BATCH, slots), jnp.int32),
        ((BATCH,), jnp.int32))]
    text = jax.jit(decode_window).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    shapes = "|".join(re.escape(",".join(map(str, s))) for s in (
        (pool, page, HKV, D), (pool * HKV, page, D)))
    moved = re.findall(rf"= \w+\[({shapes})\]\S* (copy|copy-start|"
                       rf"transpose|fusion)\(", text)
    assert not moved, moved


def test_decode_attention_compiles(one_chip):
    fn = functools.partial(decode_attention, interpret=False)
    _compile(fn, one_chip, ((BATCH, HQ, D), jnp.bfloat16),
             ((BATCH, MAX_LEN, HKV, D), jnp.bfloat16),
             ((BATCH, MAX_LEN, HKV, D), jnp.bfloat16),
             ((BATCH,), jnp.int32))


def test_flash_attention_compiles(one_chip):
    fn = functools.partial(flash_attention, causal=True, interpret=False)
    _compile(fn, one_chip, ((1, HQ, MAX_LEN, D), jnp.bfloat16),
             ((1, HKV, MAX_LEN, D), jnp.bfloat16),
             ((1, HKV, MAX_LEN, D), jnp.bfloat16))


def test_matmul_compiles(one_chip):
    fn = functools.partial(matmul, interpret=False)
    _compile(fn, one_chip, ((2048, 3072), jnp.bfloat16),
             ((3072, 8192), jnp.bfloat16))
