"""GQA/MQA attention with three interchangeable inner loops:

- ``naive``   — materialized scores; exact oracle; used for decode (Sq=1) and
                roofline-mode compiles (no inner while loop -> exact
                cost_analysis; identical matmul FLOPs to chunked).
- ``chunked`` — double lax.scan (q blocks x kv blocks) online softmax; the
                paper's `nest` blocking in pure JAX: differentiable, O(bq*bkv)
                memory, default for train/prefill.
- ``pallas``  — the flash-attention kernel (TPU target; oracle-checked).

All support causal masks, sliding windows, softcap, GQA grouping and an
absolute position offset (decode / right-aligned caches).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.models import common

NEG_INF = -1e30


class AttnParams(NamedTuple):
    impl: str = "chunked"          # naive | chunked | pallas
    causal: bool = True
    window: Optional[int] = None
    softcap: Optional[float] = None
    scale: Optional[float] = None
    # None = derive from the tuned KernelPlan for this call's shape/dtype
    # (repro.tune — the closed tune->execute loop); ints pin the blocks.
    bq: Optional[int] = None
    bkv: Optional[int] = None


def resolve_blocks(p: AttnParams, q, k) -> tuple:
    """(bq, bkv) for a blocked impl: explicit AttnParams win; ``None`` falls
    back to the cached :class:`repro.tune.KernelPlan` for
    ``(Sq, Skv, D, dtype)`` — the autotuner's choice applied as the default."""
    if p.bq is not None and p.bkv is not None:
        return p.bq, p.bkv
    from repro.tune import plan_for
    plan = plan_for("flash_attention",
                    shape_sig=(q.shape[1], k.shape[1], q.shape[-1]),
                    dtype=str(q.dtype))
    return (p.bq if p.bq is not None else plan.bq,
            p.bkv if p.bkv is not None else plan.bkv)


def _mask(q_pos, k_pos, causal, window, kv_valid_len=None):
    m = jnp.ones(jnp.broadcast_shapes(q_pos.shape, k_pos.shape), bool)
    if causal:
        m &= q_pos >= k_pos
    if window is not None:
        m &= (q_pos - k_pos) < window
    if kv_valid_len is not None:
        m &= k_pos < kv_valid_len
    return m


def naive_attention(q, k, v, p: AttnParams, q_offset=0, kv_valid_len=None,
                    k_positions=None):
    """q: (B,Sq,Hq,D); k/v: (B,Skv,Hkv,D) -> (B,Sq,Hq,D).

    ``q_offset`` / ``kv_valid_len``: scalar or per-batch (B,) — continuous
    batching serves requests at different positions in one step.
    ``k_positions``: explicit kv positions (B, Skv) for ring-buffer caches
    (negative = empty slot).
    """
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    g = hq // hkv
    scale = p.scale if p.scale is not None else d ** -0.5
    qg = q.reshape(b, sq, hkv, g, d)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    s = common.softcap(s, p.softcap)
    q_off = jnp.reshape(jnp.asarray(q_offset, jnp.int32), (-1, 1, 1))
    q_pos = q_off + jnp.arange(sq, dtype=jnp.int32)[None, :, None]  # (B?,sq,1)
    if k_positions is None:
        k_pos = jnp.arange(skv, dtype=jnp.int32)[None, None, :]
    else:
        k_pos = jnp.asarray(k_positions, jnp.int32)[:, None, :]     # (B,1,skv)
    kvl = (None if kv_valid_len is None
           else jnp.reshape(jnp.asarray(kv_valid_len, jnp.int32), (-1, 1, 1)))
    m = _mask(q_pos, k_pos, p.causal, p.window, kvl)
    if k_positions is not None:
        m &= k_pos >= 0
    s = jnp.where(m[:, None, None], s, NEG_INF)   # (B?,hkv,g,sq,skv)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", pr, v.astype(jnp.float32))
    return o.reshape(b, sq, hq, d).astype(q.dtype)


def paged_gather_attention(q, k_pages, v_pages, page_table, p: AttnParams,
                           q_offset, kv_valid_len, k_scale=None,
                           v_scale=None):
    """Chunked-prefill (extend) attention over a paged KV cache.

    q: (B, C, Hq, D) — a prompt *chunk* at absolute offset ``q_offset``;
    k/v_pages: (P, page, Hkv, D); page_table: (B, N).  The table is
    dereferenced with a dense gather — logical page j of row b covers
    absolute positions ``[j*page, (j+1)*page)``, so the gathered view is
    position-exact and the oracle's causal mask + ``kv_valid_len`` apply
    unchanged.  ``k_scale``/``v_scale`` (P, page) dequantize int8 pages per
    token.  Decode (C=1) uses the Pallas ``paged_attention`` kernel
    instead; prefill chunks are wide enough that the gather amortizes (the
    paper's unit-size rule is already baked into the page size).
    """
    b, n = page_table.shape
    page = k_pages.shape[1]
    kd = k_pages[page_table]
    vd = v_pages[page_table]
    if k_scale is not None:
        kd = kd.astype(jnp.float32) * k_scale[page_table][..., None, None]
        vd = vd.astype(jnp.float32) * v_scale[page_table][..., None, None]
    kd = kd.reshape(b, n * page, *k_pages.shape[2:])
    vd = vd.reshape(b, n * page, *v_pages.shape[2:])
    return naive_attention(q, kd.astype(q.dtype), vd.astype(q.dtype), p,
                           q_offset=q_offset, kv_valid_len=kv_valid_len)


# ---------------------------------------------------------------------------
# TP shard_map islands over the paged dispatches
# ---------------------------------------------------------------------------
# The serve-side tensor-parallel split (the paper's multi-bank / channel-
# interleaving axis): attention heads and the KV page pools partition over
# one mesh axis, page tables and valid lengths replicate, and each shard
# walks ITS OWN slice of the pools — every device streams pages from its
# own HBM stack, so aggregate KV bandwidth scales with the axis size.
# Placement is explicit (shard_map, not GSPMD inference) because the Pallas
# kernel dereferences the table to copy pages itself: the partitioner
# cannot see that page ids are head-invariant, so left to itself it would
# all-gather the pools.  GQA stays shard-local: with tp dividing both Hq
# and Hkv, contiguous head blocks keep every query group and its kv head on
# the same shard (group size g = Hq/Hkv is shard-invariant).

def tp_shardable(mesh, axis: str, hq: int, hkv: int) -> bool:
    """True when the paged dispatches can run as per-shard islands."""
    if mesh is None or axis not in mesh.shape:
        return False
    tp = mesh.shape[axis]
    return tp > 1 and hq % tp == 0 and hkv % tp == 0


def _tp_island(mesh, axis, body, args, in_specs, out_spec):
    fn = jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                       out_specs=out_spec, check_vma=False)
    return fn(*args)


def tp_paged_attention(mesh, axis: str, q, k_pages, v_pages, page_table,
                       valid_len, *, scale=None, softcap=None, window=None,
                       k_scale=None, v_scale=None, plan=None):
    """Decode-mode island: q (B, Hq, D) and the pools partition on heads,
    table/valid_len replicate; each shard dispatches the Pallas kernel over
    its head slice.  Output stays head-partitioned — the o-projection's
    contraction (GSPMD) reduces across shards."""
    from jax.sharding import PartitionSpec as P
    quant = k_scale is not None

    def body(q_, kp_, vp_, tbl_, vl_, *sc):
        ks_, vs_ = sc if quant else (None, None)
        return kops.paged_attention(q_, kp_, vp_, tbl_, vl_, scale=scale,
                                    softcap=softcap, window=window,
                                    k_scale=ks_, v_scale=vs_, plan=plan)

    pool = P(None, None, axis, None)
    args = [q, k_pages, v_pages, page_table, valid_len]
    specs = [P(None, axis, None), pool, pool, P(None, None), P(None)]
    if quant:
        args += [k_scale, v_scale]
        specs += [P(None, None), P(None, None)]
    return _tp_island(mesh, axis, body, args, specs, P(None, axis, None))


def tp_paged_gather_attention(mesh, axis: str, q, k_pages, v_pages,
                              page_table, p: AttnParams, q_offset,
                              kv_valid_len, k_scale=None, v_scale=None):
    """Extend/verify-mode island: q (B, C, Hq, D) partitions on heads; the
    dense table gather runs per shard over its own pool slice, so chunked
    prefill and multi-token verify never move another shard's pages."""
    from jax.sharding import PartitionSpec as P
    quant = k_scale is not None

    def body(q_, kp_, vp_, tbl_, off_, vl_, *sc):
        ks_, vs_ = sc if quant else (None, None)
        return paged_gather_attention(q_, kp_, vp_, tbl_, p, q_offset=off_,
                                      kv_valid_len=vl_, k_scale=ks_,
                                      v_scale=vs_)

    pool = P(None, None, axis, None)
    args = [q, k_pages, v_pages, page_table, q_offset, kv_valid_len]
    specs = [P(None, None, axis, None), pool, pool, P(None, None), P(None),
             P(None)]
    if quant:
        args += [k_scale, v_scale]
        specs += [P(None, None), P(None, None)]
    return _tp_island(mesh, axis, body, args, specs,
                      P(None, None, axis, None))


def chunked_attention(q, k, v, p: AttnParams, q_offset=0, kv_valid_len=None):
    """Online-softmax double scan (the `nest` transformation) with a
    flash-style custom VJP: the backward recomputes score blocks from
    (q, k, v, out, lse) residuals instead of letting autodiff save every
    inner-scan accumulator (which costs O(nq*nkv) fp32 blocks per layer).
    Non-divisible lengths are padded internally and masked out."""
    orig_sq, orig_skv = q.shape[1], k.shape[1]
    bq, bkv = resolve_blocks(p, q, k)
    bq = min(bq, orig_sq)
    bkv = min(bkv, orig_skv)
    pad_q = (-orig_sq) % bq
    pad_kv = (-orig_skv) % bkv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        if kv_valid_len is None:
            kv_valid_len = orig_skv
    meta = _FlashMeta(
        causal=p.causal, window=p.window, softcap=p.softcap,
        scale=p.scale if p.scale is not None else q.shape[-1] ** -0.5,
        bq=bq, bkv=bkv, q_offset=int(q_offset),
        kv_valid_len=None if kv_valid_len is None else int(kv_valid_len))
    out = _flash(meta, q, k, v)
    return out[:, :orig_sq]


class _FlashMeta(NamedTuple):
    causal: bool
    window: Optional[int]
    softcap: Optional[float]
    scale: float
    bq: int
    bkv: int
    q_offset: int
    kv_valid_len: Optional[int]


def _blocks(meta, q, k, v):
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    g = hq // hkv
    nq, nkv = sq // meta.bq, skv // meta.bkv
    qb = jnp.moveaxis(
        q.reshape(b, nq, meta.bq, hkv, g, d).astype(jnp.float32)
        * meta.scale, 1, 0)
    kb = jnp.moveaxis(k.reshape(b, nkv, meta.bkv, hkv, d).astype(jnp.float32), 1, 0)
    vb = jnp.moveaxis(v.reshape(b, nkv, meta.bkv, hkv, d).astype(jnp.float32), 1, 0)
    return qb, kb, vb, (b, sq, hq, d, skv, hkv, g, nq, nkv)


def _block_scores(meta, q_blk, k_blk, qi, kj):
    """returns (s_capped, dsoftcap, mask) for block (qi, kj)."""
    s = jnp.einsum("bqhgd,bkhd->bqhgk", q_blk, k_blk)
    if meta.softcap is not None:
        s_c = common.softcap(s, meta.softcap)
        dsoft = 1.0 - jnp.square(s_c / meta.softcap)
    else:
        s_c, dsoft = s, None
    q_pos = meta.q_offset + qi * meta.bq + jnp.arange(meta.bq)[:, None]
    k_pos = kj * meta.bkv + jnp.arange(meta.bkv)[None, :]
    msk = _mask(q_pos, k_pos, meta.causal, meta.window, meta.kv_valid_len)
    return s_c, dsoft, msk[None, :, None, None, :]


def _flash_fwd_impl(meta: _FlashMeta, q, k, v):
    qb, kb, vb, (b, sq, hq, d, skv, hkv, g, nq, nkv) = _blocks(meta, q, k, v)

    def q_step(_, q_blk_i):
        q_blk, qi = q_blk_i

        def kv_step(carry, kv_blk_j):
            m_p, l_p, acc = carry
            k_blk, v_blk, kj = kv_blk_j
            s_c, _, msk = _block_scores(meta, q_blk, k_blk, qi, kj)
            s_c = jnp.where(msk, s_c, NEG_INF)
            m_n = jnp.maximum(m_p, jnp.max(s_c, axis=-1))
            pr = jnp.exp(s_c - m_n[..., None])
            alpha = jnp.exp(m_p - m_n)
            l_n = l_p * alpha + jnp.sum(pr, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bqhgk,bkhd->bqhgd", pr, v_blk)
            return (m_n, l_n, acc), None

        init = (jnp.full((b, meta.bq, hkv, g), NEG_INF, jnp.float32),
                jnp.zeros((b, meta.bq, hkv, g), jnp.float32),
                jnp.zeros((b, meta.bq, hkv, g, d), jnp.float32))
        (m_f, l_f, acc), _ = jax.lax.scan(
            kv_step, init, (kb, vb, jnp.arange(nkv)))
        out_i = acc / jnp.maximum(l_f, 1e-30)[..., None]
        # +LARGE on empty rows so recomputed p underflows to exactly 0
        lse_i = jnp.where(l_f > 0, m_f + jnp.log(jnp.maximum(l_f, 1e-30)),
                          jnp.float32(1e30))
        return None, (out_i, lse_i)

    _, (ob, lseb) = jax.lax.scan(q_step, None, (qb, jnp.arange(nq)))
    out = jnp.moveaxis(ob, 0, 1).reshape(b, sq, hq, d).astype(q.dtype)
    return out, lseb  # lseb: (nq, b, bq, hkv, g) fp32


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(meta: _FlashMeta, q, k, v):
    return _flash_fwd_impl(meta, q, k, v)[0]


def _flash_fwd(meta, q, k, v):
    out, lseb = _flash_fwd_impl(meta, q, k, v)
    return out, (q, k, v, out, lseb)


def _flash_bwd(meta, res, dout):
    q, k, v, out, lseb = res
    qb, kb, vb, (b, sq, hq, d, skv, hkv, g, nq, nkv) = _blocks(meta, q, k, v)
    dob = jnp.moveaxis(
        dout.reshape(b, nq, meta.bq, hkv, g, d).astype(jnp.float32), 1, 0)
    outb = jnp.moveaxis(
        out.reshape(b, nq, meta.bq, hkv, g, d).astype(jnp.float32), 1, 0)
    # D_i = rowsum(dO ∘ O)
    db = jnp.sum(dob * outb, axis=-1)  # (nq, b, bq, hkv, g)

    def q_step(carry, xs):
        dk_acc, dv_acc = carry            # (nkv, b, bkv, hkv, d) fp32
        q_blk, do_blk, d_blk, lse_blk, qi = xs

        def kv_step(inner, kv_blk_j):
            dq_i, dk_acc, dv_acc = inner
            k_blk, v_blk, kj = kv_blk_j
            s_c, dsoft, msk = _block_scores(meta, q_blk, k_blk, qi, kj)
            pr = jnp.where(msk, jnp.exp(s_c - lse_blk[..., None]), 0.0)
            dv_j = jnp.einsum("bqhgk,bqhgd->bkhd", pr, do_blk)
            dp = jnp.einsum("bqhgd,bkhd->bqhgk", do_blk, v_blk)
            ds = pr * (dp - d_blk[..., None])
            if dsoft is not None:
                ds = ds * dsoft
            dq_i = dq_i + jnp.einsum("bqhgk,bkhd->bqhgd", ds, k_blk)
            dk_j = jnp.einsum("bqhgk,bqhgd->bkhd", ds, q_blk)
            dk_acc = dk_acc.at[kj].add(dk_j)
            dv_acc = dv_acc.at[kj].add(dv_j)
            return (dq_i, dk_acc, dv_acc), None

        dq0 = jnp.zeros((b, meta.bq, hkv, g, d), jnp.float32)
        (dq_i, dk_acc, dv_acc), _ = jax.lax.scan(
            kv_step, (dq0, dk_acc, dv_acc), (kb, vb, jnp.arange(nkv)))
        return (dk_acc, dv_acc), dq_i

    zeros_kv = jnp.zeros((nkv, b, meta.bkv, hkv, d), jnp.float32)
    (dk_acc, dv_acc), dqb = jax.lax.scan(
        q_step, (zeros_kv, zeros_kv),
        (qb, dob, db, lseb, jnp.arange(nq)))
    # dq was computed on q*scale
    dq = (jnp.moveaxis(dqb, 0, 1).reshape(b, sq, hq, d)
          * meta.scale).astype(q.dtype)
    dk = jnp.moveaxis(dk_acc, 0, 1).reshape(b, skv, hkv, d).astype(k.dtype)
    dv = jnp.moveaxis(dv_acc, 0, 1).reshape(b, skv, hkv, d).astype(v.dtype)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def unrolled_attention(q, k, v, p: AttnParams, q_offset=0, kv_valid_len=None):
    """Roofline-mode impl: identical blocking/math to ``chunked`` but with
    python-unrolled block loops (no lax.scan), so XLA cost_analysis counts
    every block.  Statically skips fully-masked (causal / out-of-window)
    blocks — what a production kernel grid does."""
    orig_sq, orig_skv = q.shape[1], k.shape[1]
    bq, bkv = resolve_blocks(p, q, k)
    bq = min(bq, orig_sq)
    bkv = min(bkv, orig_skv)
    pad_q = (-orig_sq) % bq
    pad_kv = (-orig_skv) % bkv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        if kv_valid_len is None:
            kv_valid_len = orig_skv
    meta = _FlashMeta(
        causal=p.causal, window=p.window, softcap=p.softcap,
        scale=p.scale if p.scale is not None else q.shape[-1] ** -0.5,
        bq=bq, bkv=bkv, q_offset=int(q_offset),
        kv_valid_len=None if kv_valid_len is None else int(kv_valid_len))
    qb, kb, vb, (b, sq, hq, d, skv, hkv, g, nq, nkv) = _blocks(meta, q, k, v)

    outs = []
    for i in range(nq):
        q_lo = meta.q_offset + i * bq
        q_hi = q_lo + bq - 1
        m_p = jnp.full((b, bq, hkv, g), NEG_INF, jnp.float32)
        l_p = jnp.zeros((b, bq, hkv, g), jnp.float32)
        acc = jnp.zeros((b, bq, hkv, g, d), jnp.float32)
        for j in range(nkv):
            k_lo, k_hi = j * bkv, (j + 1) * bkv - 1
            if meta.causal and k_lo > q_hi:
                continue  # block entirely in the future
            if meta.window is not None and (q_lo - k_hi) >= meta.window:
                continue  # block entirely out of the window
            if meta.kv_valid_len is not None and k_lo >= meta.kv_valid_len:
                continue
            # the named scope lets core.roofline attribute these bytes to the
            # kernel-fusable inner loop (VMEM-resident in the Pallas version)
            with jax.named_scope("flash_inner"):
                s_c, _, msk = _block_scores(meta, qb[i], kb[j], i, j)
                s_c = jnp.where(msk, s_c, NEG_INF)
                m_n = jnp.maximum(m_p, jnp.max(s_c, axis=-1))
                pr = jnp.exp(s_c - m_n[..., None])
                alpha = jnp.exp(m_p - m_n)
                l_p = l_p * alpha + jnp.sum(pr, axis=-1)
                acc = acc * alpha[..., None] + jnp.einsum(
                    "bqhgk,bkhd->bqhgd", pr, vb[j])
                m_p = m_n
        outs.append(acc / jnp.maximum(l_p, 1e-30)[..., None])
    out = jnp.stack(outs)  # (nq, b, bq, hkv, g, d)
    out = jnp.moveaxis(out, 0, 1).reshape(b, sq, hq, d)
    return out[:, :orig_sq].astype(q.dtype)


def pallas_attention(q, k, v, p: AttnParams, q_offset=0, kv_valid_len=None):
    assert q_offset == 0 and kv_valid_len is None, (
        "pallas path serves full-block prefill; decode uses naive")
    bq, bkv = resolve_blocks(p, q, k)
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    o = kops.flash_attention(
        qt, kt, vt, causal=p.causal, window=p.window, softcap=p.softcap,
        scale=p.scale, bq=min(bq, q.shape[1]), bkv=min(bkv, k.shape[1]))
    return jnp.swapaxes(o, 1, 2)


IMPLS = {
    "naive": naive_attention,
    "chunked": chunked_attention,
    "unrolled": unrolled_attention,
    "pallas": pallas_attention,
}


def attention(q, k, v, p: AttnParams, q_offset=0, kv_valid_len=None):
    if q.shape[1] == 1:  # decode: one query — naive is optimal
        return naive_attention(q, k, v, p, q_offset, kv_valid_len)
    return IMPLS[p.impl](q, k, v, p, q_offset, kv_valid_len)
