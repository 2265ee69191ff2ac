"""The dense GQA SwiGLU decoder: the program's model for a configuration
file of this family, the draw of its weights, its plain float32 reference
and its operation count.

The reference imports nothing of the program.  It reads the weights the
benchmark drew (``model.make_params`` with ``draw``) by their names in the
program's parameter layout: ``embed.tok`` (V, d), the stacked layers
``blocks.p0.*`` with a leading layer axis, ``final_norm`` and, when
embeddings are not tied, ``lm_head`` (d, V).  Norm gains are held as their
offset from 1.

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

The operation count (2 operations per multiply-add) covers every matrix
product of a layer, attention over each token's causal context (``q k``
and ``p v``), and the LM head once per served token (the first from the
prompt's last position, the rest from decode steps).  Not counted:
padding of a prefill chunk to its bucket, masked slots of a decode window,
the embedding lookup, norms, rotary and softmax.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

# ----------------------------------------------------------------------
# the program's model
# ----------------------------------------------------------------------
# file key -> the program's ModelConfig field
_FIELDS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
}
# what the program's dense decoder fixes and the file has to state alike
_FIXED = {"hidden_act": "silu", "partial_rotary_factor": 1.0,
          "rope_scaling": None, "rms_norm_eps": 1e-6}


def build(conf: dict):
    """The program's model for ``conf``: its registry entry for
    ``conf["arch"]`` with the file's sizes, and the serving runtime flags
    of ``repro.launch.serve.build_bundle``."""
    from repro.launch.serve import build_bundle
    from repro.models import build as build_model

    for key, want in _FIXED.items():
        if conf[key] != want:
            raise ValueError(f"{conf['arch']}: {key}={conf[key]!r}, but the "
                             f"program's dense decoder runs {want!r}")
    base = build_bundle(conf["arch"])
    if base.cfg.activation != "swiglu" or base.cfg.family != "dense":
        raise ValueError(f"{conf['arch']} is not a dense SwiGLU decoder")
    sizes = {field: conf[key] for key, field in _FIELDS.items()}
    cfg = dataclasses.replace(base.cfg, **sizes,
                              param_dtype=conf["dtype"],
                              compute_dtype=conf["dtype"])
    return build_model(cfg, base.flags)


# ----------------------------------------------------------------------
# the weights
# ----------------------------------------------------------------------
NORMS = ("ln1", "ln2", "final_norm")


def draw(conf: dict, names, shape, key):
    """One weight, float32, from its parameter path ``names`` and
    ``shape``: matrices truncated normal with std ``1/sqrt(fan_in)`` (the
    embedding ``1/sqrt(hidden_size)``); norm gains ``1 + 0.1 N(0, 1)``,
    held as the offset from 1 that the program's RMSNorm adds to 1."""
    if names[-1] in NORMS:
        return 0.1 * jax.random.normal(key, shape, jnp.float32)
    std = (conf["hidden_size"] ** -0.5 if names[0] == "embed"
           else 1.0 / math.sqrt(shape[-2]))
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                             jnp.float32)


# ----------------------------------------------------------------------
# the reference
# ----------------------------------------------------------------------
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


def _conf_items(conf: dict) -> tuple:
    """The file's model keys as a hashable static argument."""
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rope_theta", "partial_rotary_factor", "rms_norm_eps")
    return tuple((k, conf[k]) for k in keys)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _hidden(items, prec, params, tokens):
    conf = dict(items)
    x = params["embed"]["tok"][tokens].astype(jnp.float32)

    def body(x, w):
        return _layer(conf, prec, x, w), None

    x, _ = lax.scan(body, x, params["blocks"]["p0"])
    return _rms(x, params["final_norm"], conf["rms_norm_eps"])


def hidden(conf: dict, prec: str, params, tokens):
    """Final-normed hidden states (S, d) of ``tokens`` (S,), float32.
    Causal: right padding changes no earlier row."""
    return _hidden(_conf_items(conf), prec, params, tokens)


def logits(prec: str, params, h):
    """The LM head over hidden states ``h`` (n, d), in ``prec``."""
    w = (params["lm_head"] if "lm_head" in params
         else params["embed"]["tok"].T)
    return _mm(h, w, prec)


# ----------------------------------------------------------------------
# the operation count
# ----------------------------------------------------------------------
def layer_matmul(conf: dict) -> int:
    """Matrix-product operations of one layer for one token."""
    d, f = conf["hidden_size"], conf["intermediate_size"]
    hq = conf["num_attention_heads"] * conf["head_dim"]
    hkv = conf["num_key_value_heads"] * conf["head_dim"]
    return 2 * (d * hq + 2 * d * hkv + hq * d + 3 * d * f)


def attention(conf: dict, start: int, n: int) -> int:
    """Attention operations of one layer for the tokens at positions
    ``start .. start + n - 1``, each reading its ``position + 1`` keys."""
    keys = n * start + n * (n + 1) // 2
    return 4 * conf["num_attention_heads"] * conf["head_dim"] * keys


def head(conf: dict) -> int:
    return 2 * conf["hidden_size"] * conf["vocab_size"]


def tokens(conf: dict, start: int, n: int) -> int:
    """Operations of ``n`` tokens fed at positions ``start ..``: every
    layer's matrix products and attention (no LM head)."""
    layers = conf["num_hidden_layers"]
    return layers * (n * layer_matmul(conf) + attention(conf, start, n))


def served(conf: dict, prompt: int, first: int, count: int) -> int:
    """Operations behind output tokens ``first .. first + count - 1`` of a
    request with a ``prompt``-token prompt: token 0 needs the whole prompt
    fed, token ``j >= 1`` needs token ``j - 1`` fed at position
    ``prompt + j - 1``; each needs one LM head."""
    if count <= 0:
        return 0
    ops = count * head(conf)
    if first == 0:
        ops += tokens(conf, 0, prompt)
        first, count = 1, count - 1
    return ops + tokens(conf, prompt + first - 1, count)


def decode_attention(conf: dict, prompt: int, first: int, count: int):
    """Operations and bytes of decode attention behind output tokens
    ``first .. first + count - 1`` (token 0 comes from the prompt and has
    none): each decode token fed at position ``p`` reads the ``p + 1``
    cached keys and values of every layer, ``kv_heads x head_dim`` each,
    in the configuration's dtype (2 bytes)."""
    lo = max(first, 1)
    n = first + count - lo
    if n <= 0:
        return 0, 0
    start = prompt + lo - 1
    keys = n * start + n * (n + 1) // 2
    layers = conf["num_hidden_layers"]
    kv = conf["num_key_value_heads"] * conf["head_dim"]
    ops = layers * attention(conf, start, n)
    return ops, layers * keys * kv * 2 * 2


def counts(conf: dict, prompt: int, first: int, count: int) -> dict:
    """The named counts behind output tokens ``first .. first + count - 1``
    that the metric readers read: ``flops`` (``served``), ``attn_flops``
    and ``attn_bytes`` (``decode_attention``)."""
    ops, nbytes = decode_attention(conf, prompt, first, count)
    return {"flops": served(conf, prompt, first, count),
            "attn_flops": ops, "attn_bytes": nbytes}
