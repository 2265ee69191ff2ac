"""Paged-KV decode attention: block-table indirection inside the kernel.

The serving-memory version of the paper's random-access engine: the KV cache
lives in a global page pool (num_pages, page, Hkv, D) and each sequence owns
a per-sequence page table — the kernel's BlockSpec index_map dereferences the
scalar-prefetched table (``table[b, j]``), exactly the mechanism
``random_gather`` benchmarks (r_acc over page-sized units: the advisor's
"unit_bytes: row width >= 512B" guidance is why pages are >= 16 tokens).

Three serving-path extensions share the one kernel body:

- ``softcap`` — gemma2-style logit soft-capping applied to the raw scores
  before masking (mirrors the dense kernels' ``attn_logit_softcap``).
- ``window`` — *ring* tables for sliding-window layers: the table holds
  ``ring_slots = ceil(window/page)+1`` rotating slots and the kernel
  recovers each slot's absolute positions from ``valid_len`` alone
  (slot ``j`` holds logical page ``L_j = cur_L - ((cur_L - j) mod R)``),
  masking both the causal bound and the window's trailing edge — stale
  tokens left from a rotated-out page land on "future" positions and mask
  away for free.
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


def _kernel(table_ref, vlen_ref, q_ref, kp_ref, vp_ref, *rest,
            scale: float, page: int, n_pages: int, hkv: int,
            softcap: Optional[float], window: Optional[int], quant: bool):
    if quant:
        ks_ref, vs_ref = rest[0], rest[1]
        o_ref, m_ref, l_ref, acc_ref = rest[2:]
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bh = pl.program_id(0)
    b = bh // hkv
    valid = vlen_ref[b]

    q = q_ref[0].astype(jnp.float32) * scale                 # (g, d)
    k = kp_ref[0].astype(jnp.float32)                        # (page, d)
    v = vp_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (g, page)
    if quant:
        s = s * ks_ref[0]          # (1, page): token j's scale on column j
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    if window is None:
        base = j * page
    else:
        # ring slot j currently holds logical page L_j = the largest
        # L <= cur_L with L % ring_slots == j (negative L => not yet live)
        cur_l = (valid - 1) // page
        delta = jax.lax.rem(cur_l - j, n_pages)
        delta = jnp.where(delta < 0, delta + n_pages, delta)
        base = (cur_l - delta) * page
    pos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    msk = (pos < valid) & (pos >= 0)
    if window is not None:
        msk &= pos > valid - 1 - window
    s = jnp.where(msk, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    # mask p explicitly: a fully-masked page visited while m is still at its
    # NEG_INF init (a rotated-out ring slot) must contribute exactly zero
    p = jnp.where(msk, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    pv = p * vs_ref[0] if quant else p
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(pv, v)
    m_ref[...] = m_new

    @pl.when(j == n_pages - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


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
    they are masked by valid_len); valid_len: (B,) -> (B, Hq, D).

    ``window`` switches the table to *ring* semantics (N = ring slots,
    positions derived from valid_len; see module docstring).  ``k_scale``/
    ``v_scale`` (P, page) fp32 dequantize int8 pages in-kernel.

    ``plan`` (a :class:`repro.tune.KernelPlan`, hashable => static) carries
    the tuned backend choice; unlike flash/decode it cannot re-block the
    kernel here — ``plan.page_size`` shaped the pool this call receives, so
    the block IS the page and the kernel asserts the two agree.
    ``interpret=None`` resolves plan-first, then the shared auto heuristic."""
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
    b, hq, d = q.shape
    pool, page, hkv, _ = k_pages.shape
    _, n_pages = page_table.shape
    g = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    quant = k_scale is not None

    qf = q.reshape(b * hkv, g, d)
    # flatten pages per kv head: (P*Hkv, page, d)
    kf = jnp.swapaxes(k_pages, 1, 2).reshape(pool * hkv, page, d)
    vf = jnp.swapaxes(v_pages, 1, 2).reshape(pool * hkv, page, d)

    def page_map(bh, j, table_ref, vlen_ref, hkv=hkv):
        b_ = bh // hkv
        h_ = bh % hkv
        return (table_ref[b_, j] * hkv + h_, 0, 0)

    def scale_map(bh, j, table_ref, vlen_ref, hkv=hkv):
        return (table_ref[bh // hkv, j], 0, 0)

    in_specs = [
        pl.BlockSpec((1, g, d), lambda bh, j, t, vl: (bh, 0, 0)),
        pl.BlockSpec((1, page, d),
                     lambda bh, j, t, vl: page_map(bh, j, t, vl)),
        pl.BlockSpec((1, page, d),
                     lambda bh, j, t, vl: page_map(bh, j, t, vl)),
    ]
    args = [qf, kf, vf]
    if quant:
        # (P, 1, page): a (1, page) block spans the array's last two dims,
        # which the TPU tiling rule accepts for any page; (P, page) with a
        # (1, page) block would put 1 on the sublane dim
        in_specs += [
            pl.BlockSpec((1, 1, page),
                         lambda bh, j, t, vl: scale_map(bh, j, t, vl)),
        ] * 2
        args += [sc.astype(jnp.float32).reshape(pool, 1, page)
                 for sc in (k_scale, v_scale)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b * hkv, n_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, g, d), lambda bh, j, t, vl: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, page=page, n_pages=n_pages,
                          hkv=hkv, softcap=softcap, window=window,
                          quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hkv, g, d), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(page_table.astype(jnp.int32), valid_len.astype(jnp.int32), *args)
    return out.reshape(b, hq, d)
