"""Per-kernel interpret-mode sweeps vs the ref.py jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def _arr(shape, dtype=jnp.float32):
    x = RNG.standard_normal(shape)
    if dtype == jnp.int8:
        return jnp.asarray((x * 32).clip(-127, 127), jnp.int8)
    return jnp.asarray(x, dtype)


def _close(a, b, tol):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("shape", [(128, 128), (256, 512), (64, 384)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8])
@pytest.mark.parametrize("block_rows", [8, 64])
def test_stream_copy(shape, dtype, block_rows):
    if shape[0] % block_rows:
        pytest.skip("non-divisible")
    x = _arr(shape, dtype)
    _close(ops.stream_copy(x, block_rows=block_rows), ref.stream_copy(x), 0)


@pytest.mark.parametrize("mode", ["copy", "rw"])
def test_stream_modes(mode):
    x = _arr((128, 256))
    _close(ops.stream_copy(x, block_rows=32, mode=mode),
           ref.stream_copy(x, mode), 0)


@pytest.mark.parametrize("stride", [1, 2, 3, 7, 15])
@pytest.mark.parametrize("block_rows", [4, 16])
def test_strided(stride, block_rows):
    x = _arr((256, 64))
    _close(ops.strided_copy(x, block_rows=block_rows, stride=stride),
           ref.strided_copy(x, block_rows=block_rows, stride=stride), 0)


@pytest.mark.parametrize("n_idx", [16, 100])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gather(n_idx, dtype):
    x = _arr((512, 128), dtype)
    idx = ops.lfsr_indices(n_idx, bits=16) % 512
    _close(ops.random_gather(x, idx), ref.random_gather(x, idx), 0)


@pytest.mark.parametrize("n", [64, 256, 1000])
def test_chase(n):
    table = ops.make_chain(n, seed=n)
    steps = min(2 * n, 300)
    got = ops.pointer_chase(table, steps=steps)
    _close(got, ref.pointer_chase(table, steps), 0)


def test_chase_is_full_cycle():
    n = 128
    table = ops.make_chain(n, seed=1)
    trace = np.asarray(ref.pointer_chase(table, n))[:, 0]
    assert sorted(trace.tolist()) == list(range(n))  # visits every entry once


@pytest.mark.parametrize("mnk", [(128, 128, 128), (256, 128, 384), (64, 256, 128)])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4), (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("blocks", [(64, 64, 64), (128, 128, 128)])
def test_matmul(mnk, dtype, tol, blocks):
    m, k, n = mnk
    bm, bn, bk = blocks
    if m % min(bm, m) or n % min(bn, n) or k % min(bk, k):
        pytest.skip("non-divisible")
    x, y = _arr((m, k), dtype), _arr((k, n), dtype)
    _close(ops.matmul(x, y, bm=bm, bn=bn, bk=bk), ref.matmul(x, y), tol)


@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("opts", [
    dict(),
    dict(window=96),
    dict(softcap=30.0),
    dict(causal=False),
    dict(window=64, softcap=20.0),
])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-4), (jnp.bfloat16, 3e-2)])
def test_flash_attention(hq, hkv, opts, dtype, tol):
    b, s, d = 2, 256, 64
    q = _arr((b, hq, s, d), dtype)
    k = _arr((b, hkv, s, d), dtype)
    v = _arr((b, hkv, s, d), dtype)
    got = ops.flash_attention(q, k, v, bq=64, bkv=64, **opts)
    want = ref.attention(q, k, v, **opts)
    _close(got, want, tol)


def test_flash_attention_cross_lengths():
    q = _arr((1, 2, 128, 32))
    k = _arr((1, 2, 256, 32))
    v = _arr((1, 2, 256, 32))
    got = ops.flash_attention(q, k, v, causal=False, bq=64, bkv=64)
    want = ref.attention(q, k, v, causal=False)
    _close(got, want, 2e-4)


# ---------------------------------------------------------------------------
# PR 3 parity sweep: dtypes x non-default blocks x non-divisible shapes
# (the ragged-length wrapper pads to the grid and masks in-kernel)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-4),
                                       (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("bq,bkv", [(32, 32), (64, 128), (128, 64)])
@pytest.mark.parametrize("sq,skv", [(96, 96), (37, 53), (128, 100), (65, 129)])
def test_flash_attention_parity_sweep(dtype, tol, bq, bkv, sq, skv):
    b, h, d = 1, 2, 32
    q = _arr((b, h, sq, d), dtype)
    k = _arr((b, h, skv, d), dtype)
    v = _arr((b, h, skv, d), dtype)
    got = ops.flash_attention(q, k, v, causal=False, bq=bq, bkv=bkv)
    _close(got, ref.attention(q, k, v, causal=False), tol)


@pytest.mark.parametrize("opts", [dict(), dict(window=48),
                                  dict(softcap=12.0)])
@pytest.mark.parametrize("sq", [33, 100])
def test_flash_attention_causal_ragged(opts, sq):
    """satellite: odd sequence lengths no longer trip the block-divisibility
    assert — padded inside the wrapper, masked in-kernel."""
    b, h, d = 2, 2, 16
    q = _arr((b, h, sq, d))
    k = _arr((b, h, sq, d))
    v = _arr((b, h, sq, d))
    got = ops.flash_attention(q, k, v, bq=32, bkv=32, **opts)
    _close(got, ref.attention(q, k, v, **opts), 2e-4)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("bkv", [32, 96, 256])
@pytest.mark.parametrize("t", [100, 255, 256])
def test_decode_attention_parity_sweep(dtype, tol, bkv, t):
    b, hq, hkv, d = 2, 4, 2, 32
    q = _arr((b, hq, d), dtype)
    k = _arr((b, t, hkv, d), dtype)
    v = _arr((b, t, hkv, d), dtype)
    vlen = jnp.asarray([min(7, t), t], jnp.int32)
    got = ops.decode_attention(q, k, v, vlen, bkv=bkv)
    _close(got, ref.decode_attention(q, k, v, vlen), tol)


def test_kernels_accept_tuned_plan_defaults():
    """tentpole: with no blocks given, kernels resolve the cached KernelPlan
    and still match their oracle."""
    from repro.tune import PlanCache, set_default_cache
    set_default_cache(PlanCache(None))
    try:
        q, k, v = _arr((1, 2, 60, 16)), _arr((1, 2, 60, 16)), _arr((1, 2, 60, 16))
        _close(ops.flash_attention(q, k, v),
               ref.attention(q, k, v), 2e-4)
        qd, kd, vd = _arr((2, 4, 16)), _arr((2, 90, 2, 16)), _arr((2, 90, 2, 16))
        vlen = jnp.asarray([13, 90], jnp.int32)
        _close(ops.decode_attention(qd, kd, vd, vlen),
               ref.decode_attention(qd, kd, vd, vlen), 1e-4)
        x, y = _arr((96, 100)), _arr((100, 64))
        _close(ops.matmul(x, y), ref.matmul(x, y), 1e-4)
    finally:
        set_default_cache(None)


def test_lfsr_properties():
    idx = np.asarray(ops.lfsr_indices(4096, bits=16))
    assert idx.min() >= 0 and idx.max() < (1 << 16)
    # maximal-length LFSR: no repeats within the period
    assert len(np.unique(idx)) == len(idx)


@pytest.mark.parametrize("vlens", [[7, 130, 256], [1, 64, 255]])
@pytest.mark.parametrize("hq,hkv", [(8, 2), (4, 4)])
def test_decode_attention(vlens, hq, hkv):
    b, t, d = 3, 256, 32
    q = _arr((b, hq, d))
    k = _arr((b, t, hkv, d))
    v = _arr((b, t, hkv, d))
    vlen = jnp.asarray(vlens, jnp.int32)
    got = ops.decode_attention(q, k, v, vlen, bkv=64)
    want = ref.decode_attention(q, k, v, vlen)
    _close(got, want, 1e-4)


def test_decode_attention_softcap():
    b, t, hq, hkv, d = 2, 128, 4, 2, 16
    q, k, v = _arr((b, hq, d)), _arr((b, t, hkv, d)), _arr((b, t, hkv, d))
    vlen = jnp.asarray([50, 128], jnp.int32)
    got = ops.decode_attention(q, k, v, vlen, bkv=32, softcap=10.0)
    want = ref.decode_attention(q, k, v, vlen, softcap=10.0)
    _close(got, want, 1e-4)


def test_paged_attention_matches_contiguous():
    from repro.serve.kvcache import PagedKVCache
    b, t, hq, hkv, d = 3, 256, 8, 2, 32
    q, k, v = _arr((b, hq, d)), _arr((b, t, hkv, d)), _arr((b, t, hkv, d))
    vlen = jnp.asarray([7, 130, 256], jnp.int32)
    pool = PagedKVCache(num_pages=32, page_size=32, num_kv_heads=hkv,
                        head_dim=d)
    for i in range(b):
        pool.alloc(i)
        pool.append(i, k[i, :int(vlen[i])], v[i, :int(vlen[i])])
    table, vl = pool.batch_view([0, 1, 2])
    got = ops.paged_attention(q, pool.k_pages, pool.v_pages, table, vl)
    want = ref.decode_attention(q, k, v, vlen)
    _close(got, want, 1e-4)
    # oracle for the paged layout itself
    _close(ref.paged_attention(q, pool.k_pages, pool.v_pages, table, vl),
           want, 1e-4)


# ---------------------------------------------------------------------------
# paged_attention serving paths: softcap, ring windows, int8 pages
# (satellite parity sweep — fp32/bf16 x non-divisible lengths vs ref.py)
# ---------------------------------------------------------------------------

def _fill_pool(k, v, vlen, page, window=None, dtype=None, width=None):
    """Append per-sequence k/v (B, T, Hkv, D) into a fresh page pool.

    Table entries past a slot's pages hold the next slot's live page ids
    (not the null page), so a kernel that read past ``valid_len`` would
    change the answer."""
    from repro.serve.kvcache import PagedKVCache
    b, t, hkv, d = k.shape
    pool = PagedKVCache(num_pages=4 + b * (t // page + 1), page_size=page,
                        num_kv_heads=hkv, head_dim=d,
                        dtype=dtype or str(k.dtype), window=window)
    for i in range(b):
        pool.alloc(i)
        pool.append(i, k[i, :int(vlen[i])], v[i, :int(vlen[i])])
    table, vl = pool.batch_view(list(range(b)), width)
    table = np.array(table)
    for i in range(b):
        other = pool.tables[(i + 1) % b] or [0]
        for j in range(len(pool.tables[i]), table.shape[1]):
            table[i, j] = other[j % len(other)]
    return pool, jnp.asarray(table), vl


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("vlens", [[7, 100, 256], [1, 53, 255],
                                   [31, 32, 33], [255, 1, 256]])
def test_paged_attention_softcap(dtype, tol, vlens):
    """satellite: the paged kernel's softcap path (gemma2) vs the dense
    oracle, across dtypes and non-divisible lengths."""
    b, t, hq, hkv, d = 3, 256, 8, 2, 32
    q, k, v = _arr((b, hq, d), dtype), _arr((b, t, hkv, d), dtype), \
        _arr((b, t, hkv, d), dtype)
    vlen = jnp.asarray(vlens, jnp.int32)
    pool, table, vl = _fill_pool(k, v, vlen, page=32)
    got = ops.paged_attention(q, pool.k_pages, pool.v_pages, table, vl,
                              softcap=20.0)
    want = ref.decode_attention(q, k, v, vlen, softcap=20.0)
    _close(got, want, tol)
    _close(ref.paged_attention(q, pool.k_pages, pool.v_pages, table, vl,
                               softcap=20.0), want, tol)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("window,vlens", [(32, [7, 100, 250]),
                                          (24, [1, 33, 256]),
                                          (24, [15, 16, 17]),
                                          (250, [255, 1, 256])])
def test_paged_attention_ring_window(dtype, tol, window, vlens):
    """Ring tables: the pool holds only ceil(window/page)+1 pages per
    sequence, yet attention over the live window is exact."""
    b, t, hq, hkv, d = 3, 256, 4, 2, 32
    q, k, v = _arr((b, hq, d), dtype), _arr((b, t, hkv, d), dtype), \
        _arr((b, t, hkv, d), dtype)
    vlen = jnp.asarray(vlens, jnp.int32)
    pool, table, vl = _fill_pool(k, v, vlen, page=16, window=window)
    for i in range(b):
        assert len(pool.tables[i]) <= pool.ring_slots
    got = ops.paged_attention(q, pool.k_pages, pool.v_pages, table, vl,
                              window=window)
    # dense windowed oracle: naive attention with explicit kv positions
    # (the ring layout never materializes the full sequence)
    from repro.models.attention import AttnParams, naive_attention
    kpos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))
    kpos = jnp.where(kpos < vl[:, None], kpos, -10**9)
    dense = naive_attention(q[:, None], k, v,
                            AttnParams(window=window),
                            q_offset=vl - 1, k_positions=kpos)[:, 0]
    _close(got, dense, tol)
    _close(ref.paged_attention(q, pool.k_pages, pool.v_pages, table, vl,
                               window=window), dense, tol)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("vlens", [[7, 100, 250], [1, 64, 255],
                                   [31, 32, 33], [255, 1, 256]])
def test_paged_attention_int8_pages_match_dense_int8(dtype, tol, vlens):
    """satellite: int8 pages + per-token scale lanes dequantized in-kernel
    == dense int8-KV attention (quantize once, dequantize outside)."""
    from repro.models.transformer import _kv_quant
    b, t, hq, hkv, d = 3, 256, 8, 2, 32
    q = _arr((b, hq, d), dtype)
    k, v = _arr((b, t, hkv, d), dtype), _arr((b, t, hkv, d), dtype)
    vlen = jnp.asarray(vlens, jnp.int32)
    kq, ks_tok = _kv_quant(k)
    vq, vs_tok = _kv_quant(v)
    page = 32
    pool, table, vl = _fill_pool(kq, vq, vlen, page=page, dtype="int8")
    ks = jnp.zeros((pool.num_pages, page), jnp.float32)
    vs = jnp.zeros((pool.num_pages, page), jnp.float32)
    for i in range(b):
        for li, pid in enumerate(pool.tables[i]):
            n = min(page, int(vlen[i]) - li * page)
            ks = ks.at[pid, :n].set(ks_tok[i, li * page:li * page + n])
            vs = vs.at[pid, :n].set(vs_tok[i, li * page:li * page + n])
    got = ops.paged_attention(q, pool.k_pages, pool.v_pages, table, vl,
                              k_scale=ks, v_scale=vs)
    # dense int8-KV oracle: dequantize the whole cache, then attend
    kd = (kq.astype(jnp.float32) * ks_tok[..., None, None]).astype(dtype)
    vd = (vq.astype(jnp.float32) * vs_tok[..., None, None]).astype(dtype)
    want = ref.decode_attention(q, kd, vd, vlen)
    _close(got, want, tol)
    _close(ref.paged_attention(q, pool.k_pages, pool.v_pages, table, vl,
                               k_scale=ks, v_scale=vs), want, tol)


@pytest.mark.parametrize("hkv,g,window", [(1, 1, None), (1, 3, 300),
                                          (2, 1, 300), (2, 3, None),
                                          (8, 1, None), (8, 3, 300)])
@pytest.mark.parametrize("kv,tol", [("float32", 1e-4), ("bfloat16", 3e-2),
                                    ("int8", 1e-4)])
def test_paged_attention_blocks(hkv, g, window, kv, tol):
    """satellite: multi-page blocks, all KV heads a step, with softcap —
    valid lengths of 1, page-1, page, a block +-1 token and the whole
    table, every table entry past a slot's pages another slot's live page,
    against dense attention over the logical sequence."""
    from repro.kernels.paged_attention import pages_per_block
    from repro.models.attention import AttnParams, naive_attention
    from repro.models.transformer import _kv_quant
    page, d = 8, 128
    blk = page * pages_per_block(page, 1 << 20, hkv, d, kv)
    t = blk + 2 * page
    vlen = jnp.asarray([1, page - 1, page, blk - 1, blk, blk + 1, t],
                       jnp.int32)
    b = vlen.shape[0]
    qdt = jnp.bfloat16 if kv == "bfloat16" else jnp.float32
    q = _arr((b, hkv * g, d), qdt)
    k, v = _arr((b, t, hkv, d), qdt), _arr((b, t, hkv, d), qdt)
    width = None if window else t // page
    scales = {}
    if kv == "int8":
        (k, ks), (v, vs) = _kv_quant(k), _kv_quant(v)
        sp, _, _ = _fill_pool(ks[..., None, None], vs[..., None, None],
                              vlen, page, window, width=width)
        scales = dict(k_scale=sp.k_pages[..., 0, 0],
                      v_scale=sp.v_pages[..., 0, 0])
        kd = k.astype(jnp.float32) * ks[..., None, None]
        vd = v.astype(jnp.float32) * vs[..., None, None]
    else:
        kd, vd = k, v
    pool, table, vl = _fill_pool(k, v, vlen, page, window, width=width)
    got = ops.paged_attention(q, pool.k_pages, pool.v_pages, table, vl,
                              softcap=30.0, window=window, **scales)
    kpos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))
    kpos = jnp.where(kpos < vl[:, None], kpos, -10**9)
    want = naive_attention(q[:, None], kd.astype(qdt), vd.astype(qdt),
                           AttnParams(window=window, softcap=30.0),
                           q_offset=vl - 1, k_positions=kpos)[:, 0]
    _close(got, want, tol)


def test_paged_pool_alloc_release():
    from repro.serve.kvcache import PagedKVCache
    pool = PagedKVCache(num_pages=4, page_size=8, num_kv_heads=1, head_dim=8)
    pool.alloc(0)
    pool.append(0, jnp.ones((20, 1, 8)), jnp.ones((20, 1, 8)))
    assert pool.pages_in_use == 3 and pool.lengths[0] == 20
    pool.alloc(1)
    pool.append(1, jnp.ones((8, 1, 8)), jnp.ones((8, 1, 8)))
    assert pool.pages_in_use == 4
    with pytest.raises(MemoryError):
        pool.append(1, jnp.ones((8, 1, 8)), jnp.ones((8, 1, 8)))
    pool.release(0)
    assert pool.pages_in_use == 1
