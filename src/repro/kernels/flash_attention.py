"""Blockwise online-softmax attention (paper pattern: *nest* — interleaved
multi-cursor sequential traversal, Table 9).

The paper's `nest` row reaches full sequential bandwidth because both cursors
are blocked so the inner stream stays buffered; flash-attention blocking is
exactly that transformation, so this kernel is the paper's technique applied
to the framework's dominant memory consumer.

Block sizes default to the tuned :class:`repro.tune.KernelPlan` for the call
shape (the closed tune->execute loop); ``interpret`` defaults to auto —
compile on a real TPU backend, interpret elsewhere.  Ragged sequence lengths
are padded to the block grid inside the wrapper and masked in-kernel, so odd
prompt lengths never crash the grid arithmetic.

Grid = (batch*q_heads, q_blocks, kv_blocks); kv is the innermost (sequential)
dimension so the f32 (m, l, acc) scratch carries across kv steps.  Supports
causal masking, sliding windows (gemma2 / recurrentgemma local layers), GQA
head grouping, and attn-logit softcap (gemma2, grok).  Forward only — training
uses the differentiable chunked XLA path in ``repro.models.attention`` (same
math; this kernel is oracle-checked against it).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 scale: float, causal: bool, window: Optional[int],
                 softcap: Optional[float], bq: int, bkv: int, n_kv: int,
                 kv_len: int):
    kv_idx = pl.program_id(2)

    @pl.when(kv_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # (bq, d)
    k = k_ref[0].astype(jnp.float32)                   # (bkv, d)
    v = v_ref[0].astype(jnp.float32)                   # (bkv, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bkv)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    q_idx = pl.program_id(1)
    q_pos = q_idx * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
    k_pos = kv_idx * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    # ragged pad: kv rows past the true length are grid filler, never attended
    mask = k_pos < kv_len
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                             # (bq, bkv)
    alpha = jnp.exp(m_prev - m_new)                    # (bq, 1)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(p, v)
    m_ref[...] = m_new

    @pl.when(kv_idx == n_kv - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "scale", "bq", "bkv", "interpret"))
def _flash_call(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool,
                window: Optional[int], softcap: Optional[float], scale: float,
                bq: int, bkv: int, interpret: bool) -> jax.Array:
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0
    g = hq // hkv

    # ragged lengths: pad up to the block grid; the kernel masks k_pos >= skv
    # and the padded q rows are sliced off below
    pad_q = (-sq) % bq
    pad_kv = (-skv) % bkv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
    sq_p, skv_p = sq + pad_q, skv + pad_kv
    n_kv = skv_p // bkv

    qf = q.reshape(b * hq, sq_p, d)
    kf = k.reshape(b * hkv, skv_p, d)
    vf = v.reshape(b * hkv, skv_p, d)

    out = pl.pallas_call(
        functools.partial(
            _attn_kernel, scale=scale, causal=causal, window=window,
            softcap=softcap, bq=bq, bkv=bkv, n_kv=n_kv, kv_len=skv),
        grid=(b * hq, sq_p // bq, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bkv, d), lambda h, i, j, g=g: (h // g, j, 0)),
            pl.BlockSpec((1, bkv, d), lambda h, i, j, g=g: (h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(qf, kf, vf)
    return out.reshape(b, hq, sq_p, d)[:, :, :sq]


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    scale: Optional[float] = None,
                    bq: Optional[int] = None, bkv: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    plan=None) -> jax.Array:
    """q: (B, Hq, Sq, D); k/v: (B, Hkv, Skv, D); Hq % Hkv == 0.

    ``bq``/``bkv``/``interpret`` left as ``None`` resolve from the cached
    :class:`repro.tune.KernelPlan` for ``(Sq, Skv, D, dtype)`` (pass ``plan``
    to supply one explicitly); ``interpret=None`` ultimately auto-detects the
    backend (compile on TPU, interpret elsewhere).
    """
    b, hq, sq, d = q.shape
    skv = k.shape[2]
    scale = scale if scale is not None else d ** -0.5
    if bq is None or bkv is None or (plan is not None and interpret is None):
        if plan is None:
            from repro.tune import plan_for
            plan = plan_for("flash_attention", shape_sig=(sq, skv, d),
                            dtype=str(q.dtype))
        bq = bq if bq is not None else plan.bq
        bkv = bkv if bkv is not None else plan.bkv
        if interpret is None:
            interpret = plan.resolve_interpret()
    if interpret is None:
        from repro.tune import auto_interpret
        interpret = auto_interpret()
    bq = max(1, min(bq, sq))
    bkv = max(1, min(bkv, skv))
    return _flash_call(q, k, v, causal=causal, window=window, softcap=softcap,
                       scale=scale, bq=bq, bkv=bkv, interpret=bool(interpret))
