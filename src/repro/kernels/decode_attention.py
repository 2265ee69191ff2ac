"""Flash-decode kernel: one-token queries against a long KV cache.

Decode is the paper's pure-bandwidth regime (`rs_tra` over the cache): each
step streams the whole cache once.  The kernel splits the KV stream across
grid steps (split-KV / FlashDecoding style) with an online-softmax scratch
carried across the innermost grid dimension, and masks by a scalar-prefetched
per-batch valid length.  Supports GQA (q heads grouped per kv head).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(vlen_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, softcap: Optional[float], bkv: int, n_kv: int,
            hkv: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bh = pl.program_id(0)
    b = bh // hkv
    valid = vlen_ref[b]

    q = q_ref[0].astype(jnp.float32) * scale          # (g, d)
    k = k_ref[0].astype(jnp.float32)                   # (bkv, d)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (g, bkv)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    k_pos = j * bkv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(k_pos < valid, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(p, v)
    m_ref[...] = m_new

    @pl.when(j == n_kv - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("softcap", "scale", "bkv",
                                             "interpret"))
def _decode_call(q: jax.Array, k: jax.Array, v: jax.Array,
                 valid_len: jax.Array, *, softcap: Optional[float],
                 scale: float, bkv: int, interpret: bool) -> jax.Array:
    b, hq, d = q.shape
    _, t, hkv, _ = k.shape
    g = hq // hkv
    # ragged cache lengths: pad to the kv grid; padded rows sit past every
    # per-batch valid_len, so the in-kernel mask already hides them
    pad = (-t) % bkv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        t += pad
    n_kv = t // bkv

    qf = q.reshape(b * hkv, g, d)
    kf = jnp.swapaxes(k, 1, 2).reshape(b * hkv, t, d)
    vf = jnp.swapaxes(v, 1, 2).reshape(b * hkv, t, d)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * hkv, n_kv),
        in_specs=[
            pl.BlockSpec((1, g, d), lambda h, j, vl: (h, 0, 0)),
            pl.BlockSpec((1, bkv, d), lambda h, j, vl: (h, j, 0)),
            pl.BlockSpec((1, bkv, d), lambda h, j, vl: (h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, d), lambda h, j, vl: (h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, softcap=softcap, bkv=bkv,
                          n_kv=n_kv, hkv=hkv),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hkv, g, d), q.dtype),
        interpret=interpret,
        name="decode_attention",
    )(valid_len.astype(jnp.int32), qf, kf, vf)
    return out.reshape(b, hq, d)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     valid_len: jax.Array, *, softcap: Optional[float] = None,
                     scale: Optional[float] = None, bkv: Optional[int] = None,
                     interpret: Optional[bool] = None, plan=None) -> jax.Array:
    """q: (B, Hq, D); k/v: (B, T, Hkv, D); valid_len: (B,) int32 -> (B, Hq, D).

    ``bkv``/``interpret`` left as ``None`` resolve from the cached
    :class:`repro.tune.KernelPlan` for ``(T, D, dtype)`` (split-KV block =
    tuned rs_tra burst / row width); ``interpret=None`` ultimately
    auto-detects the backend.
    """
    d = q.shape[-1]
    t = k.shape[1]
    scale = scale if scale is not None else d ** -0.5
    if bkv is None or (plan is not None and interpret is None):
        if plan is None:
            from repro.tune import plan_for
            plan = plan_for("decode_attention", shape_sig=(t, d),
                            dtype=str(k.dtype))
        bkv = bkv if bkv is not None else plan.bkv
        if interpret is None:
            interpret = plan.resolve_interpret()
    if interpret is None:
        from repro.tune import auto_interpret
        interpret = auto_interpret()
    bkv = max(1, min(bkv, t))
    return _decode_call(q, k, v, valid_len, softcap=softcap, scale=scale,
                        bkv=bkv, interpret=bool(interpret))
