"""Paged-KV decode attention: block-table indirection inside the kernel.

The serving-memory version of the paper's random-access engine: the KV cache
lives in a global page pool (num_pages, page, Hkv, D) and each sequence owns
a per-sequence page table (the advisor's "unit_bytes: row width >= 512B"
guidance is why a page's head row is >= 512 bytes).

Blocking.  The grid is ``(B, ceil(N / ppb))``: one step covers ``ppb`` whole
pages of one slot, all KV heads at once.  The pools stay in HBM as they lie
(``memory_space=ANY``, no relayout around the call); a step copies its pages
one ``(page, Hkv, D)`` slab each with ``make_async_copy``, the pool page id
read from the scalar-prefetched ``table[b, .]``, into one half of a
double buffer in VMEM, and starts the next live block's copies into the
other half before it computes.  Only the pages that hold live tokens are
copied: a step whose block starts at or past ``valid_len[b]`` starts no copy
and computes nothing.  ``ppb`` is derived from the shapes (at most 256
tokens and 512 KiB of K a block: 32 pages of phi4-mini's bf16 pool), never
chosen by the caller.

A block is scored as one ``(Hq, ppb*page*Hkv)`` matrix: column ``c`` is
token ``c // Hkv`` of KV head ``c % Hkv``, and a query row only keeps the
columns of its own KV head.  That is Hkv times the MXU work of per-head
matmuls, which decode (about 3 operations a byte) has to spare, and it
reads each block's K and V once for every head.  Scores, softmax and the
accumulator are float32.

Three serving-path extensions share the one kernel body:

- ``softcap`` — gemma2-style logit soft-capping applied to the raw scores
  before masking (mirrors the dense kernels' ``attn_logit_softcap``).
- ``window`` — *ring* tables for sliding-window layers: the table holds
  ``ring_slots = ceil(window/page)+1`` rotating slots and the kernel
  recovers each slot's absolute positions from ``valid_len`` alone
  (slot ``j`` holds logical page ``L_j = cur_L - ((cur_L - j) mod R)``),
  masking both the causal bound and the window's trailing edge — stale
  tokens left from a rotated-out page land on "future" positions and mask
  away for free.  Ring tables are short, so every slot is visited.
- ``k_scale``/``v_scale`` — int8 KV pages carry a per-token fp32 scale lane
  per page ``(P, page)``; dequantization is fused into the kernel (each
  token's scale multiplies its score column and its probability column),
  so the HBM stream stays at the paper's halved unit size.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# a block's K (or V) copy: at most this many tokens and bytes.  On a v5e,
# of 128-512 tokens and 256 KiB-1 MiB, these were fastest at 560-1100 live
# tokens a slot and within 11% of the fastest at 260 and 2048; the four
# buffers stay well inside scoped VMEM
_BLOCK_TOKENS = 256
_BLOCK_BYTES = 512 * 1024


def pages_per_block(page: int, n_pages: int, hkv: int, d: int,
                    dtype) -> int:
    """Pages one grid step copies and scores: at most ``_BLOCK_TOKENS``
    tokens and ``_BLOCK_BYTES`` of K, never more than the table holds."""
    page_bytes = page * hkv * d * jnp.dtype(dtype).itemsize
    return max(1, min(n_pages, _BLOCK_TOKENS // page,
                      _BLOCK_BYTES // page_bytes))


def _kernel(table_ref, vlen_ref, q_ref, k_hbm, v_hbm, *rest,
            scale: float, page: int, n_pages: int, ppb: int, hkv: int,
            softcap: Optional[float], window: Optional[int], quant: bool):
    if quant:
        ks_hbm, vs_hbm, o_ref = rest[:3]
        kbuf, vbuf, ksbuf, vsbuf, sems, state, m_ref, l_ref, acc_ref = rest[3:]
    else:
        o_ref = rest[0]
        kbuf, vbuf, sems, state, m_ref, l_ref, acc_ref = rest[1:]
    b, j = pl.program_id(0), pl.program_id(1)
    n_slots, n_blocks = pl.num_programs(0), pl.num_programs(1)
    hq = q_ref.shape[1]
    g = hq // hkv
    tok = ppb * page                    # tokens a block
    cols = tok * hkv                    # score columns a block
    slab = k_hbm.shape[1]               # buffer rows a page: page or page*hkv

    def live_pages(bb):
        """Pages of slot ``bb`` the kernel reads: the ring's every slot, or
        the pages that hold the first ``valid_len`` tokens."""
        vl = vlen_ref[bb]
        if window is not None:
            return jnp.where(vl > 0, n_pages, 0)
        return jnp.minimum((vl + page - 1) // page, n_pages)

    def page_copies(bb, jj, slot, i):
        """The copies of page ``i`` of block (bb, jj) into buffer ``slot``."""
        pid = table_ref[bb, jj * ppb + i]
        rows = pl.ds(i * slab, slab)
        pairs = [(k_hbm.at[pid], kbuf.at[slot, rows]),
                 (v_hbm.at[pid], vbuf.at[slot, rows])]
        if quant:
            src, dst = pl.ds(pid, 1), pl.ds(i, 1)
            pairs += [(ks_hbm.at[src], ksbuf.at[slot, dst]),
                      (vs_hbm.at[src], vsbuf.at[slot, dst])]
        return [pltpu.make_async_copy(s_, d_, sems.at[slot])
                for s_, d_ in pairs]

    def each_live_page(bb, jj, slot, act):
        """``act`` on every copy of the live pages of block (bb, jj)."""
        def body(i, carry):
            for cp in page_copies(bb, jj, slot, i):
                act(cp)
            return carry

        n = jnp.minimum(ppb, live_pages(bb) - jj * ppb)
        jax.lax.fori_loop(0, n, body, 0)

    def start(bb, jj, slot):
        each_live_page(bb, jj, slot, lambda cp: cp.start())

    def wait(bb, jj, slot):
        each_live_page(bb, jj, slot, lambda cp: cp.wait())

    @pl.when((b == 0) & (j == 0))
    def _first():
        # no block in flight; dead rows of a buffer must hold finite values
        # (their probabilities are exactly 0, and 0 * NaN is not)
        state[0] = -1
        state[1] = 0
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)
        if quant:
            ksbuf[...] = jnp.zeros_like(ksbuf)
            vsbuf[...] = jnp.zeros_like(vsbuf)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * ppb < live_pages(b))
    def _block():
        slot = state[1]

        @pl.when(state[0] != b * n_blocks + j)
        def _cold():
            start(b, j, slot)

        # the next live block: this slot's next, else the next slot's first
        more = (j + 1) * ppb < live_pages(b)
        next_b = jnp.where(more, b, b + 1)
        next_j = jnp.where(more, j + 1, 0)
        has_next = more | ((b + 1 < n_slots) &
                           (live_pages(jnp.minimum(b + 1, n_slots - 1)) > 0))

        @pl.when(has_next)
        def _prefetch():
            start(next_b, next_j, 1 - slot)
            state[0] = next_b * n_blocks + next_j

        state[1] = 1 - slot
        wait(b, j, slot)

        q = q_ref[0].astype(jnp.float32) * scale               # (hq, d)
        k = kbuf[slot].astype(jnp.float32).reshape(cols, -1)   # (cols, d)
        v = vbuf[slot].astype(jnp.float32).reshape(cols, -1)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (hq, cols)
        c = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
        if quant:
            s = s * _expand(ksbuf[slot], c, page, hkv)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        valid = vlen_ref[b]
        t = c // hkv                     # token of column c in the block
        if window is None:
            pos = j * tok + t
            msk = pos < jnp.minimum(valid, n_pages * page)
        else:
            # ring slot jp holds logical page L = the largest L <= cur_L
            # with L % n_pages == jp (negative L => not yet live)
            cur_l = (valid - 1) // page
            c0 = jax.lax.rem(cur_l, n_pages)
            c0 = jnp.where(c0 < 0, c0 + n_pages, c0)
            pi = t // page
            jp = j * ppb + pi
            delta = c0 - jp
            delta = jnp.where(delta < 0, delta + n_pages, delta)
            pos = (cur_l - delta) * page + (t - pi * page)
            msk = ((pos < valid) & (pos >= 0) & (jp < n_pages)
                   & (pos > valid - 1 - window))
        if hkv > 1:
            # query row r reads only the columns of its KV head r // g
            r = jax.lax.broadcasted_iota(jnp.int32, (hq, 1), 0)
            h = (c - t * hkv) * g
            msk = msk & (r >= h) & (r < h + g)
        s = jnp.where(msk, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # mask p explicitly: a fully-masked block visited while m is still
        # at its NEG_INF init (a rotated-out ring slot) must add exactly 0
        p = jnp.where(msk, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = p * _expand(vsbuf[slot], c, page, hkv) if quant else p
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(pv, v)
        m_ref[...] = m_new

    @pl.when(j == n_blocks - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _expand(scales, c, page: int, hkv: int):
    """(ppb, lanes) per-token scales, a page a row (tokens in the first
    ``page`` lanes) -> (1, cols): the scale of column c's token.

    A one-hot matmul spreads each page's scales over its columns, then each
    column keeps the row of its own page."""
    within = jax.lax.broadcasted_iota(jnp.int32, (scales.shape[1], c.shape[1]),
                                      0)
    onehot = (within == (c // hkv) % page).astype(jnp.float32)
    spread = jax.lax.dot(scales, onehot,
                         precision=jax.lax.Precision.HIGHEST)  # (ppb, cols)
    row = jax.lax.broadcasted_iota(jnp.int32, spread.shape, 0)
    own = row == c // (hkv * page)
    return jnp.sum(jnp.where(own, spread, 0.0), axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("scale", "softcap", "window",
                                             "interpret", "plan"))
def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    page_table: jax.Array, valid_len: jax.Array, *,
                    scale: Optional[float] = None,
                    softcap: Optional[float] = None,
                    window: Optional[int] = None,
                    k_scale: Optional[jax.Array] = None,
                    v_scale: Optional[jax.Array] = None,
                    interpret: Optional[bool] = None,
                    plan=None) -> jax.Array:
    """q: (B, Hq, D); k/v_pages: (P, page, Hkv, D); page_table: (B, N) int32
    (pool page id per logical page; unused entries may be any valid id —
    they are neither read nor attended to); valid_len: (B,) -> (B, Hq, D).

    ``window`` switches the table to *ring* semantics (N = ring slots,
    positions derived from valid_len; see module docstring).  ``k_scale``/
    ``v_scale`` (P, page) fp32 dequantize int8 pages in-kernel.

    ``plan`` (a :class:`repro.tune.KernelPlan`, hashable => static) carries
    the tuned backend choice; ``plan.page_size`` shaped the pool this call
    receives, so the kernel asserts the two agree (the pages a block holds
    follow from the shapes).  ``interpret=None`` resolves plan-first, then
    the shared auto heuristic."""
    if plan is not None and k_pages.shape[1] != plan.page_size:
        raise ValueError(
            f"pool page size {k_pages.shape[1]} != plan.page_size "
            f"{plan.page_size}: the pool must be laid out from the plan")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be passed together")
    if interpret is None:
        if plan is not None:
            interpret = plan.resolve_interpret()
        else:
            from repro.tune import auto_interpret
            interpret = auto_interpret()
    b, hq, d0 = q.shape
    pool, page, hkv, _ = k_pages.shape
    _, n_pages = page_table.shape
    scale = scale if scale is not None else d0 ** -0.5
    quant = k_scale is not None
    # HBM slices of a page must span whole (sublane, lane) tiles, so two
    # kinds of pool are laid out anew (a pool-sized copy, as every call paid
    # before): a head size under 128 lanes is zero-padded to them, and a
    # KV head count under the dtype's packing (bf16 Hkv=1, int8 Hkv<4) is
    # merged into the page's rows (P, page*Hkv, D) — a bitcast for Hkv=1
    d = -(-d0 // 128) * 128
    kv = [k_pages, v_pages]
    if d != d0:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, d - d0)))
        kv = [jnp.pad(x, ((0, 0),) * 3 + ((0, d - d0),)) for x in kv]
    if hkv < 4 // jnp.dtype(k_pages.dtype).itemsize:
        kv = [x.reshape(pool, page * hkv, d) for x in kv]
    ppb = pages_per_block(page, n_pages, hkv, d, k_pages.dtype)
    n_blocks = -(-n_pages // ppb)

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((1, hq, d), lambda i, j, t, vl: (i, 0, 0)),
                hbm, hbm]
    args = [q, *kv]
    scratch = [pltpu.VMEM((2, ppb * x.shape[1], *x.shape[2:]), x.dtype)
               for x in kv]
    if quant:
        in_specs += [hbm, hbm]
        # a page's scale row is DMA'd whole: pad it to full lanes
        lanes = -(-page // 128) * 128
        args += [jnp.pad(sc.astype(jnp.float32), ((0, 0), (0, lanes - page)))
                 for sc in (k_scale, v_scale)]
        scratch += [pltpu.VMEM((2, ppb, lanes), jnp.float32)] * 2
    scratch += [
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SMEM((2,), jnp.int32),      # block id in flight, next slot
        pltpu.VMEM((hq, 1), jnp.float32),
        pltpu.VMEM((hq, 1), jnp.float32),
        pltpu.VMEM((hq, d), jnp.float32),
    ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hq, d), lambda i, j, t, vl: (i, 0, 0)),
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, page=page, n_pages=n_pages,
                          ppb=ppb, hkv=hkv, softcap=softcap, window=window,
                          quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="paged_attention",
    )(page_table.astype(jnp.int32), valid_len.astype(jnp.int32), *args)
    return out[..., :d0]
