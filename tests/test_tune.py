"""repro.tune: plan derivation, cache round-trip, invalidation, threading."""
import dataclasses
import json

import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core.memmodel import V5E, vmem_ok
from repro.tune import (KERNELS, KernelPlan, PlanCache, default_cache,
                        derive_plan, plan_for, plan_key, set_default_cache,
                        spec_fingerprint)

SIGS = {
    "flash_attention": (512, 768, 64),
    "decode_attention": (4096, 128),
    "matmul": (512, 512, 256),
    "paged_attention": (4096, 128),
    "paged_verify": (5, 4096, 128),
}


def test_top_level_namespace_export():
    """satellite: ``import repro`` exposes the tune subsystem."""
    assert repro.tune.KernelPlan is KernelPlan
    assert callable(repro.tune.plan_for)


@pytest.mark.parametrize("kernel", KERNELS)
def test_derive_plan_every_kernel(kernel):
    plan = derive_plan(kernel, shape_sig=SIGS[kernel], dtype="bfloat16")
    assert plan.kernel == kernel
    assert plan.bq >= 1 and plan.bkv >= 1
    assert plan.pipeline_depth >= 1
    assert plan.predicted_gbps > 0
    assert plan.source == "analytic"
    assert vmem_ok(plan.knobs(), V5E)
    # interpret auto-detect: None until resolved; CPU CI resolves to True
    assert plan.interpret is None
    assert plan.resolve_interpret() is True  # tests run on CPU


def test_unknown_kernel_rejected():
    with pytest.raises(ValueError):
        derive_plan("warp_attention", shape_sig=(4096, 128), dtype="bfloat16")


def test_paged_plan_page_size_is_transaction_optimum():
    """satellite: the paged plan's bkv IS the page size — the smallest pow2
    token count whose contiguous row block crosses the advisor's >= 512B
    transaction optimum (r_acc), clamped so max_len spans >= 2 pages."""
    plan = derive_plan("paged_attention", shape_sig=(4096, 128),
                       dtype="bfloat16")
    assert plan.page_size == plan.bkv
    assert plan.page_size & (plan.page_size - 1) == 0      # pow2
    assert plan.page_size * plan.head_dim * plan.dtype_bytes >= 512
    # halving the page would drop below the optimum (or below the 8 floor)
    half = plan.page_size // 2
    assert half < 8 or half * plan.head_dim * plan.dtype_bytes < 512
    # wider rows need fewer tokens per page; narrower rows need more
    wide = derive_plan("paged_attention", shape_sig=(4096, 256),
                       dtype="bfloat16")
    narrow = derive_plan("paged_attention", shape_sig=(4096, 16),
                         dtype="float32")
    assert wide.page_size <= plan.page_size <= narrow.page_size
    # a tiny max_len clamps: never a single page per sequence
    tiny = derive_plan("paged_attention", shape_sig=(16, 16), dtype="float32")
    assert tiny.page_size == 8


def test_verify_plan_rides_the_paged_page():
    """The speculative verify step reads the same pool paged decode laid
    out, so its transaction unit (bkv = the page) must match the paged
    plan for the same (max_len, head_dim, dtype); what it adds is burst
    length — bq becomes the verify width (pending + k drafts) and the
    predicted bandwidth scales with the per-transaction reuse."""
    base = derive_plan("paged_attention", shape_sig=(4096, 128),
                       dtype="bfloat16")
    for vt in (2, 5, 9):
        vplan = derive_plan("paged_verify", shape_sig=(vt, 4096, 128),
                            dtype="bfloat16")
        assert vplan.kernel == "paged_verify"
        assert vplan.bkv == base.page_size       # same pool layout
        assert vplan.bq == vt                    # burst = verify width
        assert vplan.predicted_gbps == pytest.approx(
            base.predicted_gbps * vt)
    # plan_for caches verify plans under the 3-tuple signature
    cached = plan_for("paged_verify", shape_sig=(5, 4096, 128),
                      dtype="bfloat16")
    assert cached == plan_for("paged_verify", shape_sig=(5, 4096, 128),
                              dtype="bfloat16")


def test_paged_plan_int8_widens_page_by_dtype_ratio():
    """int8 KV pages halve the unit width, so the derived page holds
    proportionally more tokens — the serving engine lays its pool out from
    the kv *storage* dtype, not the compute dtype."""
    bf16 = derive_plan("paged_attention", shape_sig=(4096, 16),
                       dtype="bfloat16")
    f32 = derive_plan("paged_attention", shape_sig=(4096, 16),
                      dtype="float32")
    int8 = derive_plan("paged_attention", shape_sig=(4096, 16), dtype="int8")
    assert int8.page_size == 2 * bf16.page_size == 4 * f32.page_size
    # same transaction bytes either way: the optimum is dtype-invariant
    assert int8.page_size * 16 * 1 >= 512
    assert bf16.page_size * 16 * 2 >= 512


def test_plan_blocks_clamped_to_shape():
    plan = derive_plan("flash_attention", shape_sig=(16, 24, 16),
                       dtype="float32")
    assert plan.bq <= 16 and plan.bkv <= 24


def test_plan_round_trips_through_json():
    plan = derive_plan("flash_attention", shape_sig=SIGS["flash_attention"],
                       dtype="bfloat16")
    again = KernelPlan.from_dict(json.loads(json.dumps(plan.to_dict())))
    assert again == plan


def test_plan_cache_persistence_round_trip(tmp_path):
    path = str(tmp_path / "tuneplans.json")
    cache = PlanCache(path)
    plan = cache.get_or_derive("flash_attention",
                               shape_sig=SIGS["flash_attention"],
                               dtype="bfloat16")
    assert len(cache) == 1
    # a fresh cache instance over the same file serves the persisted plan
    reloaded = PlanCache(path)
    key = plan_key("flash_attention", SIGS["flash_attention"], "bfloat16", V5E)
    assert reloaded.get(key) == plan
    # and get_or_derive is a pure cache hit (identical plan, count stable)
    assert reloaded.get_or_derive(
        "flash_attention", shape_sig=SIGS["flash_attention"],
        dtype="bfloat16") == plan
    assert len(reloaded) == 1


def test_plan_cache_memory_only_and_corrupt_file(tmp_path):
    mem = PlanCache(None)
    mem.get_or_derive("matmul", shape_sig=SIGS["matmul"], dtype="float32")
    assert len(mem) == 1
    bad = tmp_path / "tuneplans.json"
    bad.write_text("{not json")
    assert len(PlanCache(str(bad))) == 0  # corrupt file degrades gracefully


def test_key_invalidates_on_spec_and_calibration_change():
    """satellite/tentpole: new constants => new fingerprint => new key."""
    base_key = plan_key("flash_attention", (512, 512, 128), "bfloat16", V5E)
    other = dataclasses.replace(V5E, hbm_bw=V5E.hbm_bw * 2)
    assert spec_fingerprint(other) != spec_fingerprint(V5E)
    assert plan_key("flash_attention", (512, 512, 128), "bfloat16",
                    other) != base_key
    # dtype and shape are part of the key too
    assert plan_key("flash_attention", (512, 512, 128), "float32",
                    V5E) != base_key
    assert plan_key("flash_attention", (512, 256, 128), "bfloat16",
                    V5E) != base_key


def test_calibration_threads_into_plans():
    """A calibrated spec drives the derivation and marks the plan."""
    from repro.bench.calibrate import fit_spec, synthetic_samples
    slow = dataclasses.replace(V5E, dma_latency_s=2000e-9, hbm_bw=64e9)
    cal = fit_spec(synthetic_samples(slow))
    cache = PlanCache(None)
    plan = cache.get_or_derive("decode_attention",
                               shape_sig=SIGS["decode_attention"],
                               dtype="bfloat16", calibration=cal)
    assert plan.source == "calibrated"
    assert vmem_ok(plan.knobs(), cal.spec)
    # cached under the calibrated fingerprint, not the analytic one
    assert cache.get(plan_key("decode_attention", SIGS["decode_attention"],
                              "bfloat16", V5E)) is None


def test_default_cache_swap_and_env(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNEPLANS", str(tmp_path / "plans.json"))
    set_default_cache(None)  # force re-read of the env var
    try:
        cache = default_cache()
        assert cache.path == str(tmp_path / "plans.json")
        plan = plan_for("matmul", shape_sig=(256, 256, 256), dtype="float32")
        assert (tmp_path / "plans.json").exists()
        assert plan.kernel == "matmul"
    finally:
        set_default_cache(None)


def test_default_cache_is_memory_only_without_env(tmp_path, monkeypatch):
    """No file is read or written unless a path is asked for."""
    monkeypatch.delenv("REPRO_TUNEPLANS", raising=False)
    monkeypatch.chdir(tmp_path)
    (tmp_path / "runs").mkdir()
    set_default_cache(None)
    try:
        assert default_cache().path is None
        plan_for("matmul", shape_sig=(256, 256, 256), dtype="float32")
        assert len(default_cache()) == 1
        assert not any(tmp_path.rglob("*.json"))
    finally:
        set_default_cache(None)


def test_plan_defaults_reach_the_kernels(tmp_path, monkeypatch):
    """tentpole: kernels called with no blocks use the cached plan and still
    match the oracle (the applied-knobs path is correct end to end)."""
    from repro.kernels import ops, ref
    mem = PlanCache(None)
    set_default_cache(mem)
    try:
        rng = np.random.default_rng(3)
        q = jnp.asarray(rng.standard_normal((1, 4, 37, 16)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((1, 2, 53, 16)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((1, 2, 53, 16)), jnp.float32)
        got = ops.flash_attention(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(ref.attention(q, k, v,
                                                            causal=False)),
                                   rtol=2e-4, atol=2e-4)
        keys = list(mem.plans())
        assert any(key.startswith("flash_attention|37x53x16|") for key in keys)
    finally:
        set_default_cache(None)
