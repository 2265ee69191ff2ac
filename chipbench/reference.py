"""The plain reference: the configuration file's decoder in float32
``jax.numpy``, with no kernel, cache, page or batch.

It imports nothing of the program.  It reads the weights the benchmark
drew (``model.make_params``) by their names in the program's parameter
layout: ``embed.tok`` (V, d), the stacked layers ``blocks.p0.*`` with a
leading layer axis, ``final_norm`` and, when embeddings are not tied,
``lm_head`` (d, V).  Norm gains are held as their offset from 1.

A layer is ``x += Wo attn(rope(Wq h), rope(Wk h), Wv h)`` with
``h = rmsnorm(x)``, then ``x += Wdown (silu(Wgate h) * Wup h)`` with
``h = rmsnorm(x)``: causal softmax attention, query head ``j`` reading KV
head ``j // (heads / kv_heads)``, rotary embedding on the first
``partial_rotary_factor`` of each head's dims (each half rotated against
the other, inverse frequencies ``theta ** (-i / half)``).

``prec`` picks the arithmetic of every matrix product: ``"f32"`` is the
reference (float32 at the highest matmul precision); ``"int8"`` and
``"fp8"`` are the control, each product's operands rounded to that type
first (weights per output channel, activations per row, scaled to the
type's range), as an int8 or fp8 serving path would compute.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
NEG_INF = -1e30


def _round(x, axis, prec):
    """``x`` as the control's type would hold it, scaled along ``axis``."""
    if prec == "f32":
        return x
    amax = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30)
    if prec == "int8":
        s = amax / 127.0
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    if prec == "fp8":
        s = amax / 448.0
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(f"unknown precision {prec!r}")


def _mm(a, w, prec):
    """(..., k) @ (k, n) with ``prec``'s operands."""
    a = _round(a, -1, prec)
    w = _round(w.astype(jnp.float32), 0, prec)
    return jnp.matmul(a, w, precision=HIGHEST)


def _rms(x, gain_offset, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * (1.0 + gain_offset.astype(jnp.float32))


def _rope(x, pos, theta, factor):
    """x: (S, H, D); rotate the first ``factor * D`` dims."""
    rot = int(round(x.shape[-1] * factor))
    half = rot // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv          # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def _layer(conf, prec, x, w):
    s = x.shape[0]
    nh, nkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf["head_dim"]
    eps = conf["rms_norm_eps"]
    pos = jnp.arange(s)
    h = _rms(x, w["ln1"], eps)
    a = w["attn"]
    q = _mm(h, a["wq"], prec).reshape(s, nh, hd)
    k = _mm(h, a["wk"], prec).reshape(s, nkv, hd)
    v = _mm(h, a["wv"], prec).reshape(s, nkv, hd)
    q = _rope(q, pos, conf["rope_theta"], conf["partial_rotary_factor"])
    k = _rope(k, pos, conf["rope_theta"], conf["partial_rotary_factor"])
    g = nh // nkv
    k = jnp.repeat(k, g, axis=1)                          # head j -> j // g
    v = jnp.repeat(v, g, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", _round(q, -1, prec), _round(k, -1, prec),
                    precision=HIGHEST) * hd ** -0.5
    sc = jnp.where(pos[None, :, None] >= pos[None, None, :], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", _round(p, -1, prec), _round(v, 0, prec),
                   precision=HIGHEST).reshape(s, nh * hd)
    x = x + _mm(o, a["wo"], prec)
    h = _rms(x, w["ln2"], eps)
    m = w["mlp"]
    f = jax.nn.silu(_mm(h, m["w_gate"], prec)) * _mm(h, m["w_up"], prec)
    return x + _mm(f, m["w_down"], prec)


def head_weight(params):
    return params["lm_head"] if "lm_head" in params else params["embed"]["tok"].T


@functools.partial(jax.jit, static_argnums=(0, 1))
def hidden(conf_items, prec, params, tokens):
    """Final-normed hidden states (S, d) of ``tokens`` (S,), float32.
    Causal: right padding changes no earlier row."""
    conf = dict(conf_items)
    x = params["embed"]["tok"][tokens].astype(jnp.float32)

    def body(x, w):
        return _layer(conf, prec, x, w), None

    x, _ = lax.scan(body, x, params["blocks"]["p0"])
    return _rms(x, params["final_norm"], conf["rms_norm_eps"])


@functools.partial(jax.jit, static_argnums=(0,))
def gaps(prec, params, h_ref, h_ctl, served):
    """Per row: how far below the reference's best logit lies the served
    token, and the token the ``prec`` logits put first (the control's
    pick; with ``prec="f32"`` it is the reference's own argmax)."""
    w = head_weight(params)
    ref = jnp.matmul(h_ref, w.astype(jnp.float32), precision=HIGHEST)
    best = ref.max(-1)
    at = jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
    ctl = _mm(h_ctl, w, prec)
    pick = ctl.argmax(-1)
    at_pick = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
    return best - at, best - at_pick


def conf_items(conf: dict) -> tuple:
    """The file's model keys as a hashable static argument."""
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rope_theta", "partial_rotary_factor", "rms_norm_eps")
    return tuple((k, conf[k]) for k in keys)
