"""Paged continuous-batching backend: pool mechanics, parity, prefix cache.

The fast ones run in tier-1; the cross-backend serve-parity drains are
``@pytest.mark.slow`` and run in the CI bench-smoke job instead (they drain
two engines per config).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, override, smoke_config
from repro.models import RuntimeFlags, build
from repro.serve import (PageAllocator, PagedKVCache, PoolExhausted,
                         PrefixIndex, Request, ServeEngine, page_hashes)

FLAGS = RuntimeFlags(attn_impl="chunked", attn_bq=16, attn_bkv=16,
                     moe_impl="dense", loss_chunk=16)


# ---------------------------------------------------------------------------
# PageAllocator / PagedKVCache mechanics (satellite 1)
# ---------------------------------------------------------------------------

def test_release_raises_on_unknown_and_double_release():
    a = PageAllocator(8, 4)
    a.alloc(0)
    a.reserve(0, 6)
    a.release(0)
    with pytest.raises(KeyError):
        a.release(0)            # double release
    with pytest.raises(KeyError):
        a.release(99)           # never allocated


def test_free_list_reuse_is_deterministic_sorted():
    """Released pages are reused lowest-id-first, so page-table contents are
    reproducible run to run (the old stack-order pop was allocation-history
    dependent)."""
    a = PageAllocator(10, 4, reserved=1)
    a.alloc(0); a.reserve(0, 12)          # pages 1,2,3
    a.alloc(1); a.reserve(1, 8)           # pages 4,5
    assert a.tables[0] == [1, 2, 3] and a.tables[1] == [4, 5]
    a.release(0)
    a.alloc(2); a.reserve(2, 16)          # refills from the *sorted* holes
    assert a.tables[2] == [1, 2, 3, 6]
    a.release(1)
    a.release(2)
    assert a.free == list(range(1, 10))


def test_reserve_is_all_or_nothing_and_raises_typed():
    a = PageAllocator(4, 4)
    a.alloc(0)
    a.reserve(0, 8)                       # 2 of 4 pages
    with pytest.raises(PoolExhausted):
        a.reserve(0, 24)                  # needs 4 more, only 2 free
    assert len(a.tables[0]) == 2          # nothing partially allocated
    assert a.can_grow(0, 24) == 16        # the engine's backpressure cap
    a.reserve(0, 16)                      # the feasible target still works
    assert a.pages_in_use == 4


def test_append_spans_page_boundaries():
    pool = PagedKVCache(num_pages=5, page_size=4, num_kv_heads=1, head_dim=2)
    pool.alloc(0)
    k = jnp.arange(10 * 2, dtype=jnp.float32).reshape(10, 1, 2)
    pool.append(0, k[:3], k[:3])          # partial first page
    pool.append(0, k[3:10], k[3:10])      # spans pages 0->1->2
    assert pool.lengths[0] == 10 and len(pool.tables[0]) == 3
    table, vlen = pool.batch_view([0])
    gathered = pool.k_pages[table[0]].reshape(-1, 1, 2)[:10]
    np.testing.assert_array_equal(np.asarray(gathered), np.asarray(k))


def test_fork_copy_on_write_never_mutates_shared_pages():
    """satellite: after a fork, the first divergent append copies the shared
    page; the original bytes are bit-identical before and after."""
    pool = PagedKVCache(num_pages=8, page_size=4, num_kv_heads=1, head_dim=2)
    pool.alloc(0)
    pool.append(0, jnp.ones((6, 1, 2)), jnp.ones((6, 1, 2)))
    shared_before = np.asarray(pool.k_pages[np.asarray(pool.tables[0])])
    pool.fork(0, 1)
    assert pool.tables[1] == pool.tables[0]
    assert all(pool.is_shared(p) for p in pool.tables[0])
    pool.append(1, jnp.full((3, 1, 2), 7.0), jnp.full((3, 1, 2), 7.0))
    # the partially-filled page diverged: rid 1 got a private copy
    assert pool.tables[1][0] == pool.tables[0][0]      # full page still shared
    assert pool.tables[1][1] != pool.tables[0][1]      # COW split
    shared_after = np.asarray(pool.k_pages[np.asarray(pool.tables[0])])
    np.testing.assert_array_equal(shared_before, shared_after)
    # rid 1 sees its own timeline: old rows + the divergent append
    priv = np.asarray(pool.k_pages[pool.tables[1][1]])
    np.testing.assert_array_equal(priv[:2], shared_before[1][:2])
    assert (priv[2:] == 7.0).all()


def test_append_cow_budget_is_all_or_nothing():
    """An append that cannot afford its copy-on-write pages raises BEFORE
    mutating lengths/table — no phantom tokens claimed as valid."""
    pool = PagedKVCache(num_pages=3, page_size=4, num_kv_heads=1, head_dim=2)
    pool.alloc(0)
    pool.append(0, jnp.ones((6, 1, 2)), jnp.ones((6, 1, 2)))
    pool.fork(0, 1)
    with pytest.raises(PoolExhausted):
        # needs 1 fresh page + 1 COW copy of the shared partial page,
        # but only 1 page is free
        pool.append(1, jnp.ones((6, 1, 2)), jnp.ones((6, 1, 2)))
    assert pool.lengths[1] == 6 and len(pool.tables[1]) == 2


def test_allocator_random_ops_conserve_pages_without_hypothesis():
    """Hypothesis-free twin of the test_serve_fuzz conservation property
    (that module skips entirely when hypothesis is absent): 120 seeded
    random alloc/reserve/fork/release/truncate/evict sequences over full
    and ring allocators must conserve pages, keep refcounts >= 1, and
    respect the ring bound.  The evict op is the scheduler's preemption
    release path: truncate to the victim's live length, then release."""
    rng = np.random.default_rng(3)
    for trial in range(120):
        num_pages = int(rng.integers(4, 25))
        window = [None, 8, 13, 24][trial % 4]
        a = PageAllocator(num_pages, 4, reserved=1, window=window)
        live, next_rid = [], 0
        for _ in range(int(rng.integers(1, 40))):
            op = int(rng.integers(0, 6))
            try:
                if op == 0:
                    a.alloc(next_rid)
                    live.append(next_rid)
                    next_rid += 1
                elif op == 1 and live:
                    rid = live[int(rng.integers(0, len(live)))]
                    a.reserve(rid, a.lengths[rid] + int(rng.integers(1, 49)))
                elif op == 2 and live:
                    src = live[int(rng.integers(0, len(live)))]
                    a.fork(src, next_rid)
                    live.append(next_rid)
                    next_rid += 1
                elif op == 3 and live:
                    a.release(live.pop(int(rng.integers(0, len(live)))))
                elif op == 4 and live:
                    # speculative rollback: rewind to a random shorter length
                    rid = live[int(rng.integers(0, len(live)))]
                    a.truncate(rid, int(rng.integers(0, a.lengths[rid] + 1)))
                elif op == 5 and live:
                    # preemption eviction: truncate-then-release the victim
                    rid = live.pop(int(rng.integers(0, len(live))))
                    a.truncate(rid, a.lengths[rid] // 2)
                    a.release(rid)
            except PoolExhausted:
                pass     # backpressure is legal; corruption is not
            assert a.pages_in_use + len(a.free) == num_pages - 1
            assert all(r >= 1 for r in a.ref.values())
            if a.ring_slots is not None:
                assert all(len(t) <= a.ring_slots for t in a.tables.values())
        for rid in live:
            a.release(rid)
        assert a.pages_in_use == 0


def test_prefix_index_longest_match_and_eviction():
    a = PageAllocator(8, 4)
    a.alloc(0); a.reserve(0, 12)
    idx = PrefixIndex()
    h = page_hashes(np.arange(12), 4)
    for hh, pid in zip(h, a.tables[0]):
        idx.register(hh, pid)
        a.pin(pid)
    # a longer prompt sharing 2 pages matches exactly its leading run
    h2 = page_hashes(np.concatenate([np.arange(8), [99, 99, 99, 99]]), 4)
    assert idx.lookup(h2) == a.tables[0][:2]
    a.release(0)
    assert a.pages_in_use == 3            # pinned pages survive release
    freed = idx.evict_unused(a)
    assert freed == 3 and a.pages_in_use == 0 and len(idx) == 0


# ---------------------------------------------------------------------------
# engine: paged vs dense parity + churn (satellite 3; acceptance)
# ---------------------------------------------------------------------------

def _drain_tokens(bundle, params, *, backend, prompts, max_new, bsz=2,
                  max_len=64, **kw):
    eng = ServeEngine(bundle, params, batch_size=bsz, max_len=max_len,
                      cache_backend=backend, **kw)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.add_request(r)
    stats = eng.run_to_completion()
    return [r.out_tokens for r in reqs], stats, eng


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["gemma-2b", "phi4-mini-3.8b"])
def test_paged_matches_dense_token_for_token(arch):
    """Acceptance: greedy decode over the page pool reproduces the dense
    engine exactly — non-divisible prompt lengths, slot churn (6 requests
    through 2 slots with release/realloc reuse), chunked prefill."""
    cfg = smoke_config(ARCHS[arch])
    bundle = build(cfg, FLAGS)
    params = bundle.init(jax.random.PRNGKey(2))
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, size=int(n)).astype(np.int32)
               for n in (5, 13, 9, 27, 7, 18)]   # none divisible by page=8
    dense, sd, _ = _drain_tokens(bundle, params, backend="dense",
                                 prompts=prompts, max_new=6)
    paged, sp, eng = _drain_tokens(bundle, params, backend="paged",
                                   prompts=prompts, max_new=6,
                                   prefill_chunk=8)
    assert paged == dense
    assert sp.tokens_out == sd.tokens_out == 6 * 6
    # slot churn really released: after the drain only prefix-pinned pages
    # may persist in the pool
    assert eng.alloc.pages_in_use * eng.page <= sum(len(p) for p in prompts)


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["gemma2-27b", "recurrentgemma-9b",
                                  "mamba2-130m"])
def test_paged_matches_dense_newly_supported_stacks(arch):
    """Tentpole acceptance: ring-paged windows (gemma2), hybrid recurrent
    stacks (recurrentgemma, mamba2) reproduce the dense engine exactly
    under slot churn and chunked prefill."""
    cfg = smoke_config(ARCHS[arch])
    bundle = build(cfg, FLAGS)
    params = bundle.init(jax.random.PRNGKey(12))
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, cfg.vocab_size, size=int(n)).astype(np.int32)
               for n in (5, 13, 9, 27, 7, 18)]
    dense, sd, _ = _drain_tokens(bundle, params, backend="dense",
                                 prompts=prompts, max_new=6)
    paged, sp, eng = _drain_tokens(bundle, params, backend="paged",
                                   prompts=prompts, max_new=6,
                                   prefill_chunk=8)
    assert paged == dense
    assert sp.tokens_out == sd.tokens_out == 6 * 6
    if eng.ralloc is not None:
        assert eng.ralloc.pages_in_use == 0   # churn really released


@pytest.mark.slow
def test_paged_matches_dense_int8_kv():
    """int8 KV pages (quantized k/v + per-page scale lanes, dequant fused
    into the kernel) reproduce the dense int8 engine token for token."""
    cfg = smoke_config(ARCHS["gemma-2b"])
    flags = RuntimeFlags(attn_impl="chunked", attn_bq=16, attn_bkv=16,
                         moe_impl="dense", loss_chunk=16, kv_dtype="int8")
    bundle = build(cfg, flags)
    params = bundle.init(jax.random.PRNGKey(13))
    rng = np.random.default_rng(22)
    prompts = [rng.integers(0, cfg.vocab_size, size=int(n)).astype(np.int32)
               for n in (5, 13, 9, 27)]
    dense, _, de = _drain_tokens(bundle, params, backend="dense",
                                 prompts=prompts, max_new=6)
    paged, _, pe = _drain_tokens(bundle, params, backend="paged",
                                 prompts=prompts, max_new=6, prefill_chunk=8)
    assert paged == dense
    # int8 halves the unit size, so the derived page doubles in tokens
    assert pe.page >= 2 * ServeEngine(
        build(cfg, FLAGS), params, batch_size=1, max_len=64).page
    assert pe.live_kv_bytes_peak() < de.kv_bytes()


def test_ring_pages_bounded_and_eagerly_released():
    """The ring headline: a windowed layer's live pages never exceed
    ceil(window/page)+1 per slot however long the sequence runs — the
    trailing page is reused in place the moment the window slides past."""
    cfg = smoke_config(ARCHS["gemma2-27b"])     # (local 16, global) pattern
    bundle = build(cfg, FLAGS)
    params = bundle.init(jax.random.PRNGKey(14))
    eng = ServeEngine(bundle, params, batch_size=1, max_len=64,
                      cache_backend="paged", prefill_chunk=8)
    req = Request(rid=0, prompt=np.arange(10, dtype=np.int32),
                  max_new_tokens=40)           # runs to position 50: 7 pages
    eng.add_request(req)
    eng.run_to_completion()
    assert len(req.out_tokens) == 40
    assert eng.ring_slots == 3                  # ceil(16/8) + 1
    assert eng.stats.ring_pages_peak <= eng.ring_slots
    # the full-attention layer kept every page; the ring did not
    assert eng.stats.pages_peak >= 7
    assert eng.ralloc.pages_in_use == 0 and eng.alloc.pages_in_use == 0


def test_ring_prefill_chunk_wider_than_ring_capacity():
    """A prefill chunk spanning more logical pages than the ring has slots
    must not scatter two pages through one slot (duplicate indices have
    unspecified order): writes older than the trailing (R-1) pages steer
    to the null page instead, and outputs still match dense exactly."""
    cfg = smoke_config(ARCHS["gemma2-27b"])   # window 16, page 8, R = 3
    bundle = build(cfg, FLAGS)
    params = bundle.init(jax.random.PRNGKey(16))
    rng = np.random.default_rng(24)
    prompts = [rng.integers(0, cfg.vocab_size, size=40).astype(np.int32)]
    dense, _, _ = _drain_tokens(bundle, params, backend="dense",
                                prompts=prompts, max_new=6)
    # prefill_chunk=32 > ring capacity 24 tokens: one chunk wraps the ring
    paged, _, eng = _drain_tokens(bundle, params, backend="paged",
                                  prompts=prompts, max_new=6,
                                  prefill_chunk=32)
    assert eng.prefill_chunk > eng.ring_slots * eng.page - eng.page
    assert paged == dense


def test_hybrid_pending_prefill_state_survives_decode_windows():
    """Hybrid regression guard: a long prompt prefilling in chunks while
    another slot decodes must not have its recurrent state trampled by the
    masked decode ticks between its chunks."""
    cfg = smoke_config(ARCHS["recurrentgemma-9b"])
    bundle = build(cfg, FLAGS)
    params = bundle.init(jax.random.PRNGKey(15))
    rng = np.random.default_rng(23)
    prompts = [rng.integers(0, cfg.vocab_size, size=4).astype(np.int32),
               rng.integers(0, cfg.vocab_size, size=40).astype(np.int32)]
    dense, _, _ = _drain_tokens(bundle, params, backend="dense",
                                prompts=prompts, max_new=8)
    paged, sp, _ = _drain_tokens(bundle, params, backend="paged",
                                 prompts=prompts, max_new=8,
                                 prefill_chunk=8)
    assert paged == dense
    assert sp.prefill_chunks >= 6   # the long prompt really chunked


@pytest.mark.slow
def test_paged_matches_dense_bfloat16():
    cfg = override(smoke_config(ARCHS["gemma-2b"]), compute_dtype="bfloat16")
    bundle = build(cfg, FLAGS)
    params = bundle.init(jax.random.PRNGKey(3))
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (11, 6)]
    dense, _, _ = _drain_tokens(bundle, params, backend="dense",
                                prompts=prompts, max_new=5)
    paged, _, _ = _drain_tokens(bundle, params, backend="paged",
                                prompts=prompts, max_new=5)
    assert paged == dense


def test_paged_is_default_for_every_decoder_only_stack():
    """Tentpole: the page pool is the default backend for (nearly) every
    decoder in the registry — windowed (ring pages), recurrent hybrids
    (dense state beside the pools), pure-ssm, and int8-KV stacks included.
    Only enc-dec and frontend stacks keep the dense per-slot cache."""
    for arch in ("gemma-2b", "mamba2-130m", "gemma2-27b",
                 "recurrentgemma-9b", "phi4-mini-3.8b"):
        assert build(smoke_config(ARCHS[arch]), FLAGS).paged_supported(), arch
    int8 = build(smoke_config(ARCHS["gemma-2b"]),
                 RuntimeFlags(attn_impl="chunked", kv_dtype="int8"))
    assert int8.paged_supported()
    encdec = build(smoke_config(ARCHS["seamless-m4t-medium"]), FLAGS)
    assert not encdec.paged_supported()
    vlm = build(smoke_config(ARCHS["pixtral-12b"]), FLAGS)
    assert not vlm.paged_supported()
    params = encdec.init(jax.random.PRNGKey(0))
    eng = ServeEngine(encdec, params, batch_size=1, max_len=32)
    assert eng.backend == "dense"       # auto fallback
    with pytest.raises(ValueError):
        ServeEngine(encdec, params, batch_size=1, max_len=32,
                    cache_backend="paged")


def test_pool_exhaustion_becomes_backpressure(gemma_env=None):
    """A pool too small for the whole batch keeps requests queued (typed
    backpressure, not a crash) and still completes them as pages free."""
    cfg = smoke_config(ARCHS["gemma-2b"])
    bundle = build(cfg, FLAGS)
    params = bundle.init(jax.random.PRNGKey(4))
    # page=8, 3 usable pages: one 20-token request needs 3 -> solo admission
    eng = ServeEngine(bundle, params, batch_size=2, max_len=32,
                      num_pages=4, prefix_cache=False)
    for i in range(3):
        eng.add_request(Request(rid=i,
                                prompt=np.arange(17, dtype=np.int32) + i,
                                max_new_tokens=4))
    stats = eng.run_to_completion()
    assert stats.tokens_out == 3 * 4
    assert stats.pool_stalls > 0        # admission actually backed off
    assert eng.alloc.pages_in_use == 0


def test_impossible_prompt_raises_instead_of_silent_drop():
    """A prompt no amount of backpressure can ever admit (needs more pages
    than the pool holds) must raise loudly, not sit queued forever while
    run_to_completion returns 'drained'."""
    cfg = smoke_config(ARCHS["gemma-2b"])
    bundle = build(cfg, FLAGS)
    params = bundle.init(jax.random.PRNGKey(4))
    eng = ServeEngine(bundle, params, batch_size=1, max_len=32,
                      num_pages=3)          # 2 usable pages of 8 = 16 tokens
    eng.add_request(Request(rid=0, prompt=np.arange(17, dtype=np.int32),
                            max_new_tokens=2))
    with pytest.raises(ValueError, match="pages"):
        eng.run_to_completion()


def test_explicit_page_size_reshapes_pool_and_plan():
    """page_size overrides the derived plan; the plan handed to the kernel
    must describe the pool actually laid out (the kernel asserts it)."""
    cfg = smoke_config(ARCHS["gemma-2b"])
    bundle = build(cfg, FLAGS)
    params = bundle.init(jax.random.PRNGKey(4))
    eng = ServeEngine(bundle, params, batch_size=1, max_len=32, page_size=4)
    assert eng.page == 4 and eng.plan.page_size == 4
    req = Request(rid=0, prompt=np.arange(6, dtype=np.int32),
                  max_new_tokens=4)
    eng.add_request(req)
    eng.run_to_completion()
    assert len(req.out_tokens) == 4


def test_long_prompt_prefills_in_chunks_between_decode_ticks():
    """Chunked prefill: a long prompt admits in prefill_chunk pieces and
    in-flight decode keeps ticking between chunks."""
    cfg = smoke_config(ARCHS["gemma-2b"])
    bundle = build(cfg, FLAGS)
    params = bundle.init(jax.random.PRNGKey(5))
    eng = ServeEngine(bundle, params, batch_size=2, max_len=64, window=2,
                      prefill_chunk=8)
    short = Request(rid=0, prompt=np.arange(4, dtype=np.int32),
                    max_new_tokens=12)
    long = Request(rid=1, prompt=np.arange(40, dtype=np.int32) + 100,
                   max_new_tokens=4)
    eng.add_request(short)
    eng.add_request(long)
    stats = eng.run_to_completion()
    assert len(short.out_tokens) == 12 and len(long.out_tokens) == 4
    assert stats.prefill_chunks >= 1 + 5   # 40 tokens / 8-token chunks
    # decode went on while the long prompt was still prefilling: more
    # dispatches than a single post-prefill drain would need
    assert stats.decode_dispatches > 2


def test_kv_page_counters_follow_slot_lengths():
    """``kv_pages_live`` counts the table entries the decode kernel reads —
    ceil((pos + 1) / page) a tick for each slot that advances, none for a
    slot that waits (here a long prompt still prefilling) — as the device
    positions each window starts from say; ``kv_pages_table`` counts B x
    table width a tick.  The dense backend counts neither."""
    cfg = smoke_config(ARCHS["gemma-2b"])
    bundle = build(cfg, FLAGS)
    params = bundle.init(jax.random.PRNGKey(5))

    def drain(backend):
        eng = ServeEngine(bundle, params, batch_size=2, max_len=64,
                          window=4, prefill_chunk=8, cache_backend=backend)
        seen = []
        if backend == "paged":
            inner = eng._paged_decode_many

            def spy(n, params, cache, tokens, pos, steps, keys, table):
                seen.append((n, np.asarray(pos), np.asarray(steps)))
                return inner(n, params, cache, tokens, pos, steps, keys,
                             table)
            eng._paged_decode_many = spy
        eng.add_request(Request(rid=0, prompt=np.arange(4, dtype=np.int32),
                                max_new_tokens=20))
        eng.add_request(Request(rid=1, max_new_tokens=6,
                                prompt=np.arange(30, dtype=np.int32) + 50))
        return eng, eng.run_to_completion(), seen

    eng, stats, seen = drain("paged")
    width = eng.pages_per_seq
    live = sum(int(np.minimum(-(-(pos + t + 1) // eng.page), width)[
        t < steps].sum()) for n, pos, steps in seen for t in range(n))
    assert any((steps == 0).any() for _, _, steps in seen)
    assert stats.kv_pages_live == live > 0
    assert stats.kv_pages_table == sum(n for n, _, _ in seen) * 2 * width
    assert 0 < stats.kv_live_page_share < 1
    _, dense, _ = drain("dense")
    assert dense.kv_pages_live == dense.kv_pages_table == 0
    assert dense.kv_live_page_share == 0


# ---------------------------------------------------------------------------
# prefix caching (tentpole; satellite 3's fork test is above)
# ---------------------------------------------------------------------------

def test_prefix_cache_hits_and_outputs_unchanged():
    """Requests sharing a >= 1-page prompt prefix reuse its pages read-only:
    hit accounting moves, outputs stay bit-identical to an uncached run."""
    cfg = smoke_config(ARCHS["gemma-2b"])
    bundle = build(cfg, FLAGS)
    params = bundle.init(jax.random.PRNGKey(6))
    rng = np.random.default_rng(9)
    common = rng.integers(0, cfg.vocab_size, size=16).astype(np.int32)
    prompts = [np.concatenate([common, rng.integers(
        0, cfg.vocab_size, size=5).astype(np.int32)]) for _ in range(4)]
    # batch_size=1 serializes requests => later ones see registered pages
    cached, sc, eng = _drain_tokens(bundle, params, backend="paged",
                                    prompts=prompts, max_new=4, bsz=1)
    uncached, su, _ = _drain_tokens(bundle, params, backend="paged",
                                    prompts=prompts, max_new=4, bsz=1,
                                    prefix_cache=False)
    assert cached == uncached
    assert su.prefix_hit_tokens == 0
    assert sc.prefix_hit_tokens == 3 * 16   # requests 2..4 reuse both pages
    # shared pages survive in the pool for future hits (pinned by the index)
    assert eng.alloc.pages_in_use >= 2


def test_shared_prefix_pages_never_written_by_later_requests():
    """The engine-level never-write guarantee: page bytes registered by the
    first request are bit-identical after later requests decode over them."""
    cfg = smoke_config(ARCHS["gemma-2b"])
    bundle = build(cfg, FLAGS)
    params = bundle.init(jax.random.PRNGKey(7))
    common = (np.arange(16, dtype=np.int32) * 3 + 1) % cfg.vocab_size
    eng = ServeEngine(bundle, params, batch_size=1, max_len=64)
    eng.add_request(Request(rid=0, prompt=common, max_new_tokens=3))
    eng.run_to_completion()
    shared = sorted(eng.prefix._by_hash.values())
    assert len(shared) == 2
    def snapshot():
        leaf = jax.tree_util.tree_leaves(eng.cache)[0]
        # stacked pools carry LAYERS first: (nb, P, page, Hkv, D)
        return np.asarray(leaf[:, shared] if leaf.ndim == 5 else leaf[shared])
    before = snapshot()
    tail = np.asarray([7, 7, 7, 7, 7], np.int32)
    eng.add_request(Request(rid=1,
                            prompt=np.concatenate([common, tail]),
                            max_new_tokens=6))
    stats = eng.run_to_completion()
    assert stats.prefix_hit_tokens == 16
    np.testing.assert_array_equal(before, snapshot())


# ---------------------------------------------------------------------------
# memory figure of merit (acceptance)
# ---------------------------------------------------------------------------

def test_live_bytes_below_dense_footprint():
    """The whole point: live-token HBM bytes strictly below the dense
    ``batch x max_len`` commitment for a short-request mix."""
    cfg = smoke_config(ARCHS["gemma-2b"])
    bundle = build(cfg, FLAGS)
    params = bundle.init(jax.random.PRNGKey(8))
    prompts = [np.arange(6, dtype=np.int32) + 10 * i for i in range(4)]
    _, _, dense_eng = _drain_tokens(bundle, params, backend="dense",
                                    prompts=prompts, max_new=4, bsz=4)
    _, _, paged_eng = _drain_tokens(bundle, params, backend="paged",
                                    prompts=prompts, max_new=4, bsz=4)
    assert paged_eng.live_kv_bytes_peak() < dense_eng.live_kv_bytes_peak()
    assert paged_eng.stats.pages_peak <= paged_eng.num_pages - 1


# ---------------------------------------------------------------------------
# speculative decoding: seeded twins of the fuzz equivalence layer
# (test_serve_fuzz skips wholesale without hypothesis; these always run)
# ---------------------------------------------------------------------------

from repro.serve import SamplingParams  # noqa: E402


@pytest.fixture(scope="module")
def spec_env():
    cfg = smoke_config(ARCHS["gemma-2b"])
    bundle = build(cfg, FLAGS)
    params = bundle.init(jax.random.PRNGKey(7))
    # different params: proposals genuinely get rejected, so every drain
    # exercises suffix rollback, not just the accept-everything fast lane
    draft_params = bundle.init(jax.random.PRNGKey(11))
    return cfg, bundle, params, draft_params


def _seeded_mixes(cfg, n_mixes=3):
    """Deterministic request mixes with shared prefixes and varied budgets."""
    rng = np.random.default_rng(17)
    common = rng.integers(0, cfg.vocab_size, size=9).astype(np.int32)
    mixes = []
    for _ in range(n_mixes):
        reqs = []
        for r in range(int(rng.integers(2, 4))):
            plen = int(rng.integers(1, 13))
            tail = rng.integers(0, cfg.vocab_size, size=plen).astype(np.int32)
            prompt = (np.concatenate([common, tail])
                      if rng.integers(0, 2) else tail)
            reqs.append((prompt, int(rng.integers(1, 9))))
        mixes.append(reqs)
    return mixes


def _drive_mix(eng, mix):
    eng.reset()
    reqs = []
    first, rest = mix[:1], mix[1:]
    for prompt, max_new in first:
        r = Request(rid=len(reqs), prompt=prompt, max_new_tokens=max_new)
        reqs.append(r)
        eng.add_request(r)
    eng.step()                      # later admissions land mid-drain
    for prompt, max_new in rest:
        r = Request(rid=len(reqs), prompt=prompt, max_new_tokens=max_new)
        reqs.append(r)
        eng.add_request(r)
    eng.run_to_completion(max_ticks=5_000)
    assert all(s is None for s in eng.slots)
    return [r.out_tokens for r in reqs]


@pytest.mark.parametrize("variant", ["greedy", "sampled"])
def test_spec_matches_vanilla_seeded_mixes(spec_env, variant):
    """T=0 speculative drains are token-identical to vanilla paged drains;
    T>0 drains sharing per-slot keys are key-exact identical — and every
    drain leaves the page pool conserved after rollback churn."""
    cfg, bundle, params, draft_params = spec_env
    sampling = (None if variant == "greedy"
                else SamplingParams(temperature=0.9, top_p=0.95))
    vanilla = ServeEngine(bundle, params, batch_size=2, max_len=64,
                          cache_backend="paged", prefill_chunk=8,
                          sampling=sampling, seed=3)
    spec = ServeEngine(bundle, params, batch_size=2, max_len=64,
                       cache_backend="paged", prefill_chunk=8,
                       sampling=sampling, seed=3, draft_bundle=bundle,
                       draft_params=draft_params, spec_k=3)
    for mix in _seeded_mixes(cfg):
        want = _drive_mix(vanilla, mix)
        got = _drive_mix(spec, mix)
        assert got == want
        assert spec.stats.spec_steps > 0
        a = spec.alloc
        assert a.pages_in_use + len(a.free) == a.num_pages - a.reserved
        assert all(r >= 1 for r in a.ref.values())
    # the draft path must have seen real rejections, or this proved nothing
    assert spec.stats.draft_accepted < spec.stats.draft_tokens


def test_spec_stats_track_acceptance(spec_env):
    """Self-draft greedy: every proposal matches the coupled sample, so the
    accept rate is exactly 1 and each dispatch advances spec_k+1 tokens
    per unblocked slot (modulo end-of-budget truncation)."""
    cfg, bundle, params, _ = spec_env
    eng = ServeEngine(bundle, params, batch_size=1, max_len=64,
                      cache_backend="paged", prefill_chunk=8,
                      draft_bundle=bundle, draft_params=params, spec_k=3)
    req = Request(rid=0, prompt=np.arange(5, dtype=np.int32),
                  max_new_tokens=12)
    eng.add_request(req)
    stats = eng.run_to_completion()
    assert len(req.out_tokens) == 12
    assert stats.accept_rate == 1.0
    assert stats.spec_steps == stats.decode_dispatches
    # 12 tokens = 1 prefill seed + 11 decoded; at k+1=4/dispatch that is
    # ceil(11/4) = 3 verify dispatches
    assert stats.spec_steps == 3
    assert stats.accepted_per_step > 1.0


def test_spec_validation_errors(spec_env):
    cfg, bundle, params, draft_params = spec_env
    with pytest.raises(ValueError, match="draft_params"):
        ServeEngine(bundle, params, batch_size=1, max_len=64,
                    draft_bundle=bundle)
    ring_cfg = smoke_config(ARCHS["gemma2-27b"])     # sliding-window stack
    ring_bundle = build(ring_cfg, FLAGS)
    ring_params = ring_bundle.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="rollback"):
        ServeEngine(ring_bundle, ring_params, batch_size=1, max_len=64,
                    cache_backend="paged", draft_bundle=ring_bundle,
                    draft_params=ring_params)
    with pytest.raises(ValueError, match="paged"):
        ServeEngine(bundle, params, batch_size=1, max_len=64,
                    cache_backend="dense", draft_bundle=bundle,
                    draft_params=draft_params)


# ---------------------------------------------------------------------------
# rollback mechanics: PageAllocator.truncate (tentpole support)
# ---------------------------------------------------------------------------

def test_truncate_frees_only_private_trailing_pages():
    a = PageAllocator(10, 4, reserved=1)
    a.alloc(0)
    a.reserve(0, 14)                       # pages for 14 tokens: 4 pages
    assert len(a.tables[0]) == 4
    freed = a.truncate(0, 9)               # keep ceil(9/4) = 3 pages
    assert len(freed) == 1 and len(a.tables[0]) == 3
    assert a.pages_in_use + len(a.free) == 9
    freed = a.truncate(0, 9)               # idempotent at the same length
    assert freed == []
    with pytest.raises(ValueError):
        a.truncate(0, 10)                  # growth is reserve's job
    a.release(0)
    assert a.pages_in_use == 0


def test_truncate_never_frees_or_mutates_shared_pages():
    """Speculative rollback on a forked table: shared pages are decref'd,
    never freed early — the sibling still owns them, byte-identical."""
    a = PageAllocator(12, 4, reserved=1)
    a.alloc(0)
    a.reserve(0, 16)                       # 4 pages
    a.fork(0, 1)                           # rid 1 shares all 4
    src_table = list(a.tables[0])
    freed = a.truncate(1, 5)               # drop rid 1 back to 2 pages
    assert freed == []                     # shared: nothing returns to pool
    assert a.tables[0] == src_table        # sibling table untouched
    assert all(a.ref[p] == 2 for p in a.tables[1])
    assert all(a.ref[p] == 1 for p in src_table[2:])
    a.release(0)
    # now rid 1's remaining pages are the last references
    freed = a.truncate(1, 0)
    assert sorted(freed) == sorted(src_table[:2])
    a.release(1)
    assert a.pages_in_use == 0


def test_ring_truncate_only_rewinds_length():
    a = PageAllocator(8, 4, reserved=1, window=8)
    a.alloc(0)
    a.reserve(0, 20)                       # rotates within ring_slots pages
    held = list(a.tables[0])
    a.truncate(0, 17)
    assert a.tables[0] == held             # rotation handles regrowth
    assert a.lengths[0] == 17
    a.release(0)
    assert a.pages_in_use == 0


def test_ring_evict_never_frees_rotated_shared_page_early():
    """Satellite: the scheduler's eviction path (truncate to the live
    length, then release) on a windowed victim whose ring has rotated and
    whose pages a sibling still shares.  The sibling must keep every one
    of its pages referenced and byte-consistent through the eviction —
    rotation makes trailing slot indices ambiguous, so only refcounts
    (never position arithmetic) may decide what returns to the pool."""
    a = PageAllocator(10, 4, reserved=1, window=8)   # ring_slots = 3
    a.alloc(0)
    a.reserve(0, 20)                       # grown past the window: rotated
    victim_pages = list(a.tables[0])
    assert len(victim_pages) == a.ring_slots
    # a sibling attaches the victim's rotated table (the engine's ring
    # fork: attach a copy of the slot-indexed table at the same length)
    a.alloc(1)
    a.attach(1, list(a.tables[0]), a.lengths[0])
    assert all(a.ref[p] == 2 for p in set(victim_pages))
    before = {p: a.ref[p] for p in set(victim_pages)}

    # evict the victim mid-flight: rewind (possibly into rotated history),
    # then release its references
    a.truncate(0, 9)
    assert a.tables[0] == victim_pages     # ring truncate rewinds length only
    a.release(0)

    # the sibling's pages all survive with exactly one reference left;
    # nothing the sibling can still read was freed early
    for p in set(victim_pages):
        assert a.ref[p] == before[p] - 1 == 1
    assert not set(a.tables[1]) & set(a.free)
    assert a.pages_in_use + len(a.free) == a.num_pages - a.reserved

    # sibling continues growing through its (rotating) ring unharmed
    a.reserve(1, 24)
    assert len(a.tables[1]) <= a.ring_slots
    assert all(a.ref[p] >= 1 for p in a.tables[1])
    a.release(1)
    assert a.pages_in_use == 0


# ---------------------------------------------------------------------------
# per-slot PRNG isolation under churn (satellite)
# ---------------------------------------------------------------------------

def test_prng_stream_is_churn_invariant(spec_env):
    """A request's sampled stream depends only on (seed, rid) — masked
    ticks, pending-prefill neighbours, budget-exhausted slots, and
    mid-drain admissions must not consume its PRNG state."""
    cfg, bundle, params, _ = spec_env
    sp = SamplingParams(temperature=3.0, top_p=0.98)
    prompt0 = np.asarray([5, 9, 2, 7, 1], np.int32)

    eng = ServeEngine(bundle, params, batch_size=2, max_len=64,
                      cache_backend="paged", prefill_chunk=8,
                      sampling=sp, seed=21)
    solo_req = Request(rid=0, prompt=prompt0, max_new_tokens=10)
    eng.add_request(solo_req)
    eng.run_to_completion()

    eng.reset()
    churn_req = Request(rid=0, prompt=prompt0, max_new_tokens=10)
    eng.add_request(churn_req)
    # a long-prompt neighbour: its chunked prefill interleaves masked
    # decode ticks over rid 0's live slot
    eng.add_request(Request(rid=1, prompt=np.arange(30, dtype=np.int32),
                            max_new_tokens=2))
    for _ in range(4):
        eng.step()
    # mid-drain admissions churn slot 1 through several occupants
    eng.add_request(Request(rid=2, prompt=np.arange(7, dtype=np.int32),
                            max_new_tokens=6))
    eng.add_request(Request(rid=3, prompt=np.arange(3, dtype=np.int32),
                            max_new_tokens=4))
    eng.run_to_completion()
    assert churn_req.out_tokens == solo_req.out_tokens
