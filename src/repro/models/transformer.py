"""Decoder-only LM assembly covering the dense / moe / ssm / hybrid / vlm
families.

Layer stacks are scanned: parameters for each *pattern position* are stacked
on a leading LAYERS axis and ``lax.scan`` iterates pattern blocks (gemma2
scans (local, global) pairs; recurrentgemma scans (rec, rec, attn) triples
plus 2 unrolled remainder layers).  ``flags.unroll_layers`` switches to a
python loop for roofline-mode compiles.

Three modes: ``train`` (full seq, no cache), ``prefill`` (full seq ->
cache), ``decode`` (one token, cache in/out).  Sliding-window layers keep
ring-buffer caches of window length (this is what makes recurrentgemma's
long_500k cell constant-memory).
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ATTN, DENSE, MOE, NONE, RGLRU, SSD, LayerSpec, ModelConfig
from repro.kernels import ops as kops
from repro.models import attention as attn_mod
from repro.models import mlp as mlp_mod
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.attention import AttnParams
from repro.models.common import (EMBED, HEADS, KV_HEADS, LAYERS, VOCAB,
                                 ParamBuilder, Sharder, cross_entropy,
                                 no_shard, rms_norm, rope, softcap)


@dataclass(frozen=True)
class RuntimeFlags:
    """Execution knobs (never affect math, except kv_dtype quantization)."""

    attn_impl: str = "chunked"       # naive | chunked | pallas
    # None = blocks come from the tuned KernelPlan for the call shape
    # (repro.tune); ints pin them (tests / roofline compiles).
    attn_bq: Optional[int] = None
    attn_bkv: Optional[int] = None
    moe_impl: str = "sorted"         # dense | sorted
    moe_group: int = 1024
    remat: str = "none"              # none | full | dots
    unroll_layers: bool = False      # roofline mode
    loss_chunk: int = 512
    aux_loss_weight: float = 0.01
    kv_dtype: str = "native"         # native | int8  (decode-cache quant:
    #                                  the paper's unit-size lever on the KV
    #                                  stream — halves cache bytes)
    shd: Sharder = no_shard
    # serve-side tensor parallelism: a jax Mesh turns the paged dispatches
    # into shard_map islands (heads + KV pools partitioned over tp_axis,
    # page tables replicated — see attention.tp_paged_attention)
    mesh: Any = None
    tp_axis: str = "model"


def paged_supported(cfg: ModelConfig, kv_dtype: str = "native") -> bool:
    """The paged KV backend serves (nearly) every decoder-only stack:

    - full-attention layers grow a per-sequence page table;
    - sliding-window layers keep a *ring* of ``ceil(window/page)+1`` pages,
      rotating the trailing page in place as the window slides past it;
    - recurrent mixers (ssd/rglru) keep dense per-slot state beside the
      page pools (hybrid cache) — only attention layers read the table;
    - ``kv_dtype="int8"`` stores int8 pages with a per-token scale lane per
      page, dequantized inside the paged kernel (the paper's unit-size
      lever on the KV stream);
    - the paged kernel mirrors the dense ``attn_logit_softcap`` path.

    Only encoder-decoder stacks (split cache) and modality frontends fall
    back to the dense per-slot cache."""
    del kv_dtype  # int8 pages are first-class now; kept for call-site compat
    return not (cfg.enc_dec or cfg.frontend)


def _kv_quant(x):
    """(B,S,H,D) -> (int8, per-token scale (B,S) f32)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=(2, 3))
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[:, :, None, None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _kv_dequant(q, scale, dtype):
    return (q.astype(jnp.float32) * scale[:, :, None, None]).astype(dtype)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_layer(b: ParamBuilder, path: str, spec: LayerSpec, cfg: ModelConfig,
                stacked: int):
    lead = (stacked,) if stacked else ()
    la = (LAYERS,) if stacked else ()
    d, hd = cfg.d_model, cfg.resolved_head_dim
    b.zeros(f"{path}.ln1", lead + (d,), la + (EMBED,))
    if spec.mixer == ATTN:
        b.dense(f"{path}.attn.wq", lead + (d, cfg.num_heads * hd),
                la + (EMBED, HEADS))
        b.dense(f"{path}.attn.wk", lead + (d, cfg.num_kv_heads * hd),
                la + (EMBED, KV_HEADS))
        b.dense(f"{path}.attn.wv", lead + (d, cfg.num_kv_heads * hd),
                la + (EMBED, KV_HEADS))
        b.dense(f"{path}.attn.wo", lead + (cfg.num_heads * hd, d),
                la + (HEADS, EMBED))
    elif spec.mixer == SSD:
        ssm_mod.init(b, f"{path}.ssd", cfg, stacked)
    elif spec.mixer == RGLRU:
        rglru_mod.init(b, f"{path}.rglru", cfg, stacked)
    else:
        raise ValueError(spec.mixer)
    if spec.mlp == DENSE:
        b.zeros(f"{path}.ln2", lead + (d,), la + (EMBED,))
        mlp_mod.init(b, f"{path}.mlp", d, cfg.d_ff, cfg.activation, stacked)
    elif spec.mlp == MOE:
        b.zeros(f"{path}.ln2", lead + (d,), la + (EMBED,))
        moe_mod.init(b, f"{path}.moe", d, cfg.d_ff, cfg.num_experts,
                     cfg.activation, stacked)


def init_params(cfg: ModelConfig, key: Optional[jax.Array],
                abstract: bool = False) -> Tuple[dict, dict]:
    b = ParamBuilder(key, jnp.dtype(cfg.param_dtype), abstract=abstract)
    b.dense("embed.tok", (cfg.vocab_size, cfg.d_model), (VOCAB, EMBED),
            scale=cfg.d_model ** -0.5)
    nb = cfg.num_pattern_blocks
    for j, spec in enumerate(cfg.layer_pattern):
        _init_layer(b, f"blocks.p{j}", spec, cfg, nb)
    for j, spec in enumerate(cfg.remainder_specs):
        _init_layer(b, f"rem.r{j}", spec, cfg, 0)
    b.zeros("final_norm", (cfg.d_model,), (EMBED,))
    if not cfg.tie_embeddings:
        b.dense("lm_head", (cfg.d_model, cfg.vocab_size), (EMBED, VOCAB))
    return b.params, b.specs


# ---------------------------------------------------------------------------
# single-layer apply
# ---------------------------------------------------------------------------

def _attn_params(cfg: ModelConfig, spec: LayerSpec, flags: RuntimeFlags) -> AttnParams:
    scale = (cfg.query_pre_attn_scalar ** -0.5
             if cfg.query_pre_attn_scalar is not None
             else cfg.resolved_head_dim ** -0.5)
    return AttnParams(
        impl=flags.attn_impl, causal=True, window=spec.sliding_window,
        softcap=cfg.attn_logit_softcap, scale=scale,
        bq=flags.attn_bq, bkv=flags.attn_bkv)


def _ring_gather(cache, tbl, off, page, window, dtype):
    """Gather a ring table's live tokens into a contiguous view.

    Returns (k, v, k_positions) with k/v (B, R*page, Hkv, D) and positions
    (B, R*page) int32 (-1e9 = dead slot).  Ring slot ``j`` holds logical
    page ``cur_L - ((cur_L - j) mod R)`` where ``cur_L`` is the logical
    page of the last token *already written* (``off - 1``); stale tokens
    from rotated-out pages map to positions >= off and are masked."""
    b, r = tbl.shape
    kg = cache["k_pages"][tbl]                        # (B, R, page, Hkv, D)
    vg = cache["v_pages"][tbl]
    if "k_scale" in cache:
        kg = kg.astype(jnp.float32) * cache["k_scale"][tbl][..., None, None]
        vg = vg.astype(jnp.float32) * cache["v_scale"][tbl][..., None, None]
    cur = jnp.maximum(off - 1, 0)[:, None] // page    # (B, 1)
    j = jnp.arange(r, dtype=jnp.int32)[None, :]
    base = (cur - (cur - j) % r) * page               # (B, R)
    kpos = base[:, :, None] + jnp.arange(page, dtype=jnp.int32)[None, None, :]
    ok = (kpos < off[:, None, None]) & (kpos >= 0)
    kpos = jnp.where(ok, kpos, -10**9).reshape(b, r * page)
    kg = kg.reshape(b, r * page, *kg.shape[3:]).astype(dtype)
    vg = vg.reshape(b, r * page, *vg.shape[3:]).astype(dtype)
    return kg, vg, kpos


def _paged_attn(q, k, v, cache, ap, spec, pos, table, chunk_valid, cfg,
                flags, mode, plan=None, active=None):
    """The paged-cache mixer body (both paged modes).

    Full-attention layers read ``table["full"]`` (logical page j at absolute
    positions [j*page, (j+1)*page)); sliding-window layers read
    ``table["ring"]`` (rotating slots, positions recovered from the valid
    length).  Decode (S=1) writes the token through the table then
    dispatches the ``paged_attention`` Pallas kernel (softcap / window /
    int8-dequant paths included); extend (prefill chunks) attends over a
    gathered view — ring layers attend *before* writing, because a chunk
    crossing a page boundary rotates the trailing page that its own early
    queries still need.  A decode slot that ``active`` (B,) marks False
    (retired, pending prefill, budget spent) reads no pages: its output is
    discarded.  ``kv_dtype="int8"`` quantizes per token before the
    scatter and stores the scales in per-page lanes.  Pad positions
    (bucketed chunks, masked decode ticks on retired slots) are steered to
    page 0 — the engine reserves it as a null page, so masked writes can
    never corrupt live data.
    """
    bsz, s = q.shape[:2]
    page = cache["k_pages"].shape[1]
    ring = spec.sliding_window is not None
    tbl = table["ring"] if ring else table["full"]
    n = tbl.shape[1]
    posv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (bsz,))
    positions = posv[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    if chunk_valid is None:
        valid = jnp.full((bsz,), s, jnp.int32)
    else:
        valid = jnp.broadcast_to(
            jnp.asarray(chunk_valid, jnp.int32).reshape(-1), (bsz,))
    in_chunk = jnp.arange(s, dtype=jnp.int32)[None, :] < valid[:, None]
    writable = in_chunk
    if ring:
        pidx = (positions // page) % n
        if s > 1:
            # a chunk wider than the ring would scatter two logical pages
            # through the same slot (duplicate indices, unspecified order);
            # only the trailing (R-1) pages of positions can matter to any
            # future query ((R-1)*page >= window), and that span cannot
            # alias — everything older is steered to the null page
            end = (posv + valid)[:, None]
            writable = in_chunk & (positions >= end - (n - 1) * page)
    else:
        pidx = jnp.minimum(positions // page, n - 1)
    pids = jnp.where(writable, tbl[jnp.arange(bsz)[:, None], pidx], 0)
    slots = jnp.where(writable, positions % page, 0)

    int8kv = flags.kv_dtype == "int8"
    if int8kv:
        kq, ks = _kv_quant(k)
        vq, vs = _kv_quant(v)
        # the cache is the source of truth: attend over what readers will
        # dequantize, so chunked and one-shot prefill agree bit-for-bit
        k = _kv_dequant(kq, ks, q.dtype)
        v = _kv_dequant(vq, vs, q.dtype)
    else:
        kq, vq = k, v

    if mode != "paged_decode" and ring:
        # attend BEFORE the write: the chunk may rotate out a page its own
        # early queries still need (window trailing edge)
        kg, vg, kpos = _ring_gather(cache, tbl, posv, page,
                                    spec.sliding_window, q.dtype)
        cpos = jnp.where(in_chunk, positions, -10**9)
        k_all = jnp.concatenate([kg, k.astype(q.dtype)], axis=1)
        v_all = jnp.concatenate([vg, v.astype(q.dtype)], axis=1)
        o = attn_mod.naive_attention(q, k_all, v_all, ap, q_offset=posv,
                                     k_positions=jnp.concatenate(
                                         [kpos, cpos], axis=1))

    kp = cache["k_pages"].at[pids, slots].set(
        kq.astype(cache["k_pages"].dtype))
    vp = cache["v_pages"].at[pids, slots].set(
        vq.astype(cache["v_pages"].dtype))
    new_cache = dict(cache)
    new_cache.update(k_pages=kp, v_pages=vp)
    if int8kv:
        new_cache["k_scale"] = cache["k_scale"].at[pids, slots].set(ks)
        new_cache["v_scale"] = cache["v_scale"].at[pids, slots].set(vs)

    tp = attn_mod.tp_shardable(flags.mesh, flags.tp_axis,
                               q.shape[2], kp.shape[2])
    if mode == "paged_decode":  # S == 1: the kernel's regime
        kvalid = posv + 1
        if active is not None:
            kvalid = jnp.where(active, kvalid, 0)
        if tp:
            o = attn_mod.tp_paged_attention(
                flags.mesh, flags.tp_axis, q[:, 0], kp, vp, tbl, kvalid,
                scale=ap.scale, softcap=ap.softcap,
                window=spec.sliding_window,
                k_scale=new_cache.get("k_scale"),
                v_scale=new_cache.get("v_scale"), plan=plan)[:, None]
        else:
            o = kops.paged_attention(
                q[:, 0], kp, vp, tbl, kvalid, scale=ap.scale,
                softcap=ap.softcap, window=spec.sliding_window,
                k_scale=new_cache.get("k_scale"),
                v_scale=new_cache.get("v_scale"), plan=plan)[:, None]
    elif not ring:  # paged_extend: chunked prefill over the gathered view
        if tp:
            o = attn_mod.tp_paged_gather_attention(
                flags.mesh, flags.tp_axis, q, kp, vp, tbl, ap,
                q_offset=posv, kv_valid_len=posv + valid,
                k_scale=new_cache.get("k_scale"),
                v_scale=new_cache.get("v_scale"))
        else:
            o = attn_mod.paged_gather_attention(
                q, kp, vp, tbl, ap, q_offset=posv, kv_valid_len=posv + valid,
                k_scale=new_cache.get("k_scale"),
                v_scale=new_cache.get("v_scale"))
    return o, new_cache


def _apply_attn(p, x, cfg, spec, flags, mode, cache, pos, table=None,
                chunk_valid=None, plan=None, active=None):
    bsz, s, d = x.shape
    hd = cfg.resolved_head_dim
    shd = flags.shd
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"]).reshape(bsz, s, cfg.num_heads, hd)
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"]).reshape(bsz, s, cfg.num_kv_heads, hd)
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"]).reshape(bsz, s, cfg.num_kv_heads, hd)
    ap = _attn_params(cfg, spec, flags)

    if mode in ("paged_decode", "paged_extend"):
        o, new_cache = _paged_attn(q, k, v, cache, ap, spec, pos, table,
                                   chunk_valid, cfg, flags, mode, plan,
                                   active)
    elif mode == "decode":
        # scalar pos (batch-uniform decode, the dry-run/throughput path) uses
        # dynamic-update-slice — SPMD-friendly on seq-sharded caches; vector
        # pos (continuous batching) uses per-slot scatter.
        uniform = jnp.ndim(pos) == 0
        posv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (bsz,))
        q = rope(q, posv[:, None], cfg.rope_theta)
        k = rope(k, posv[:, None], cfg.rope_theta)

        def _store(buf, val, idx):
            val = val.astype(buf.dtype)  # rope upcasts bf16 k to f32
            if uniform:
                return jax.lax.dynamic_update_slice_in_dim(buf, val, idx, 1)
            return buf.at[jnp.arange(bsz), idx].set(val[:, 0])

        def _store_scale(buf, val, idx):
            val = val.astype(buf.dtype)
            if uniform:
                return jax.lax.dynamic_update_slice(buf, val, (0, idx))
            return buf.at[jnp.arange(bsz), idx].set(val[:, 0])

        int8kv = flags.kv_dtype == "int8"
        if int8kv:
            kq, ks = _kv_quant(k)
            vq, vs = _kv_quant(v)
        else:
            kq, ks, vq, vs = k, None, v, None

        if spec.sliding_window is not None:
            w = cache["k"].shape[1]
            slot = (pos if uniform else posv) % w
            kc = _store(cache["k"], kq, slot)
            vc = _store(cache["v"], vq, slot)
            kpos = _store_scale(
                cache["kpos"],
                jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1, 1),
                                 (bsz, 1)), slot)
            new_cache = dict(k=kc, v=vc, kpos=kpos)
            if int8kv:
                new_cache["k_scale"] = _store_scale(cache["k_scale"], ks, slot)
                new_cache["v_scale"] = _store_scale(cache["v_scale"], vs, slot)
                kc = _kv_dequant(kc, new_cache["k_scale"], k.dtype)
                vc = _kv_dequant(vc, new_cache["v_scale"], v.dtype)
            o = attn_mod.naive_attention(
                q, kc, vc, ap, q_offset=posv, k_positions=kpos)
        else:
            idx = pos if uniform else posv
            kc = _store(cache["k"], kq, idx)
            vc = _store(cache["v"], vq, idx)
            new_cache = dict(k=kc, v=vc)
            if int8kv:
                new_cache["k_scale"] = _store_scale(cache["k_scale"], ks, idx)
                new_cache["v_scale"] = _store_scale(cache["v_scale"], vs, idx)
                kc = _kv_dequant(kc, new_cache["k_scale"], k.dtype)
                vc = _kv_dequant(vc, new_cache["v_scale"], v.dtype)
            o = attn_mod.naive_attention(
                q, kc, vc, ap, q_offset=posv, kv_valid_len=posv + 1)
    else:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (bsz, s))
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        k = shd(k, ("batch", "seq", "kv_heads", None))
        v = shd(v, ("batch", "seq", "kv_heads", None))
        int8kv = flags.kv_dtype == "int8" and mode == "prefill"
        if int8kv:
            # the cache is the source of truth: prefill attends over the
            # quantize->dequantize round trip it stores, so its logits agree
            # bit-for-bit with decode (and with paged chunked prefill, which
            # can only read earlier chunks back from int8 pages)
            kq, ks = _kv_quant(k)
            vq, vs = _kv_quant(v)
            k = _kv_dequant(kq, ks, q.dtype)
            v = _kv_dequant(vq, vs, q.dtype)
        o = attn_mod.attention(q, k, v, ap)
        new_cache = None
        if mode == "prefill":
            if spec.sliding_window is not None:
                w = min(spec.sliding_window, s)
                sl = slice(s - w, None)
                new_cache = dict(
                    kpos=jnp.broadcast_to(
                        jnp.arange(s - w, s, dtype=jnp.int32)[None], (bsz, w)))
            else:
                sl = slice(None)
                new_cache = {}
            if int8kv:
                new_cache["k"], new_cache["k_scale"] = kq[:, sl], ks[:, sl]
                new_cache["v"], new_cache["v_scale"] = vq[:, sl], vs[:, sl]
            else:
                new_cache["k"], new_cache["v"] = k[:, sl], v[:, sl]
    o = o.reshape(bsz, s, cfg.num_heads * hd)
    out = jnp.einsum("bsh,hd->bsd", o, p["wo"])
    return out, new_cache


def _recurrent_chunk(mod, p, h, cache, cfg, flags, pos, slot):
    """Hybrid-cache chunked prefill through a recurrent mixer: slice the
    per-slot state row out of the batch state tree, run the chunk forward
    from it, and scatter the updated row back.  ``pos == 0`` (the first
    chunk of a freshly admitted request) restarts the state from zeros —
    the slot may hold garbage from masked decode ticks of its previous
    occupant."""
    slot = jnp.asarray(slot, jnp.int32).reshape(())
    fresh = jnp.asarray(pos, jnp.int32).reshape(-1)[0] == 0
    st = jax.tree.map(
        lambda a: jnp.where(fresh, jnp.zeros_like(a[:1]),
                            jax.lax.dynamic_slice_in_dim(a, slot, 1, 0)),
        cache)
    mix, st1 = mod.forward(p, h, cfg, flags.shd, return_state=True, state=st)
    new_cache = jax.tree.map(
        lambda full, upd: jax.lax.dynamic_update_slice_in_dim(
            full, upd.astype(full.dtype), slot, 0),
        cache, st1)
    return mix, new_cache


def _freeze_inactive(new_state, old_state, active):
    """Freeze recurrent state rows of inactive slots.  Attention pages are
    write-idempotent under a frozen position (or steered to the null page),
    but a recurrent update is not — a pending-prefill slot's partial state
    must survive the masked decode ticks between its chunks."""
    if active is None:
        return new_state
    return jax.tree.map(
        lambda n, o: jnp.where(
            jnp.reshape(active, (-1,) + (1,) * (n.ndim - 1)), n, o),
        new_state, old_state)


def _apply_layer(p, x, cfg, spec, flags, mode, cache, pos, table=None,
                 chunk_valid=None, plan=None, slot=None, active=None):
    """returns (x, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    h = rms_norm(x, p["ln1"])
    if spec.mixer == ATTN:
        mix, new_cache = _apply_attn(p["attn"], h, cfg, spec, flags, mode,
                                     cache, pos, table, chunk_valid, plan,
                                     active)
    elif spec.mixer == SSD:
        if mode in ("decode", "paged_decode"):
            mix, new_cache = ssm_mod.decode_step(p["ssd"], h, cache, cfg)
            if mode == "paged_decode":
                new_cache = _freeze_inactive(new_cache, cache, active)
        elif mode == "paged_extend":
            mix, new_cache = _recurrent_chunk(ssm_mod, p["ssd"], h, cache,
                                              cfg, flags, pos, slot)
        elif mode == "prefill":
            mix, new_cache = ssm_mod.forward(p["ssd"], h, cfg, flags.shd,
                                             return_state=True)
        else:
            mix, new_cache = ssm_mod.forward(p["ssd"], h, cfg, flags.shd), None
    elif spec.mixer == RGLRU:
        if mode in ("decode", "paged_decode"):
            mix, new_cache = rglru_mod.decode_step(p["rglru"], h, cache, cfg)
            if mode == "paged_decode":
                new_cache = _freeze_inactive(new_cache, cache, active)
        elif mode == "paged_extend":
            mix, new_cache = _recurrent_chunk(rglru_mod, p["rglru"], h, cache,
                                              cfg, flags, pos, slot)
        elif mode == "prefill":
            mix, new_cache = rglru_mod.forward(p["rglru"], h, cfg, flags.shd,
                                               return_state=True)
        else:
            mix, new_cache = rglru_mod.forward(p["rglru"], h, cfg, flags.shd), None
    else:
        raise ValueError(spec.mixer)
    x = x + mix
    x = flags.shd(x, ("batch", "seq", "embed"))

    if spec.mlp == DENSE:
        h = rms_norm(x, p["ln2"])
        x = x + mlp_mod.apply(p["mlp"], h, cfg.activation, flags.shd)
    elif spec.mlp == MOE:
        h = rms_norm(x, p["ln2"])
        out, aux = moe_mod.apply(
            p["moe"], h, cfg.num_experts_per_tok, cfg.activation,
            impl=flags.moe_impl, shd=flags.shd, group_size=flags.moe_group,
            capacity_factor=cfg.moe_capacity_factor)
        x = x + out
    x = flags.shd(x, ("batch", "seq", "embed"))
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# stack
# ---------------------------------------------------------------------------

def _empty_cache_for(cfg, spec: LayerSpec, batch: int, max_len: int, dtype,
                     kv_dtype: str = "native"):
    hd = cfg.resolved_head_dim
    if spec.mixer == ATTN:
        kvd = jnp.int8 if kv_dtype == "int8" else dtype
        t = (min(spec.sliding_window, max_len)
             if spec.sliding_window is not None else max_len)
        c = dict(k=jnp.zeros((batch, t, cfg.num_kv_heads, hd), kvd),
                 v=jnp.zeros((batch, t, cfg.num_kv_heads, hd), kvd))
        if spec.sliding_window is not None:
            c["kpos"] = jnp.full((batch, t), -10**9, jnp.int32)
        if kv_dtype == "int8":
            c["k_scale"] = jnp.zeros((batch, t), jnp.float32)
            c["v_scale"] = jnp.zeros((batch, t), jnp.float32)
        return c
    if spec.mixer == SSD:
        return ssm_mod.init_state(cfg, batch, dtype)
    if spec.mixer == RGLRU:
        return rglru_mod.init_state(cfg, batch, dtype)
    raise ValueError(spec.mixer)


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               kv_dtype: str = "native") -> dict:
    """Decode cache pytree: blocks stacked on LAYERS, remainder unstacked."""
    dtype = jnp.dtype(cfg.compute_dtype)
    nb = cfg.num_pattern_blocks

    def stack(tree):
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (nb,) + a.shape), tree)

    blocks = {f"p{j}": stack(_empty_cache_for(cfg, spec, batch, max_len,
                                              dtype, kv_dtype))
              for j, spec in enumerate(cfg.layer_pattern)}
    rem = {f"r{j}": _empty_cache_for(cfg, spec, batch, max_len, dtype, kv_dtype)
           for j, spec in enumerate(cfg.remainder_specs)}
    return dict(blocks=blocks, rem=rem)


def _empty_paged_for(cfg, spec: LayerSpec, num_pages: int, ring_pages: int,
                     page_size: int, batch: int, dtype, kv_dtype: str):
    """One layer's slice of the hybrid paged cache: page pools for attention
    (full layers share the ``num_pages`` pool, windowed layers the
    ``ring_pages`` pool), dense per-slot state for recurrent mixers."""
    if spec.mixer == SSD:
        return ssm_mod.init_state(cfg, batch, dtype)
    if spec.mixer == RGLRU:
        return rglru_mod.init_state(cfg, batch, dtype)
    if spec.mixer != ATTN:
        raise ValueError(spec.mixer)
    hd = cfg.resolved_head_dim
    p = ring_pages if spec.sliding_window is not None else num_pages
    kvd = jnp.int8 if kv_dtype == "int8" else dtype
    shape = (p, page_size, cfg.num_kv_heads, hd)
    c = dict(k_pages=jnp.zeros(shape, kvd), v_pages=jnp.zeros(shape, kvd))
    if kv_dtype == "int8":
        c["k_scale"] = jnp.zeros((p, page_size), jnp.float32)
        c["v_scale"] = jnp.zeros((p, page_size), jnp.float32)
    return c


def init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                     batch: int = 1, ring_pages: int = 0,
                     kv_dtype: str = "native") -> dict:
    """Paged decode cache: per-layer page *pools* instead of per-slot dense
    buffers.  Page ids are shared across layers of the same kind (one
    host-side allocator + table for the full-attention pools, one for the
    windowed ring pools), recurrent mixers keep dense (batch, ...) state
    rows, and ``kv_dtype="int8"`` adds a per-token fp32 scale lane per
    page.  The pytree mirrors :func:`init_cache`'s stacking — blocks on
    LAYERS, remainder unstacked — with pools/state as leaves."""
    dtype = jnp.dtype(cfg.compute_dtype)
    nb = cfg.num_pattern_blocks
    ring_pages = ring_pages or num_pages

    def stack(tree):
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (nb,) + a.shape), tree)

    blocks = {f"p{j}": stack(_empty_paged_for(cfg, spec, num_pages,
                                              ring_pages, page_size, batch,
                                              dtype, kv_dtype))
              for j, spec in enumerate(cfg.layer_pattern)}
    rem = {f"r{j}": _empty_paged_for(cfg, spec, num_pages, ring_pages,
                                     page_size, batch, dtype, kv_dtype)
           for j, spec in enumerate(cfg.remainder_specs)}
    return dict(blocks=blocks, rem=rem)


def _scan_blocks(params, x, cfg, flags, mode, cache, pos, table=None,
                 chunk_valid=None, plan=None, slot=None, active=None):
    """Apply the scanned pattern blocks + remainder layers.  ``table`` /
    ``chunk_valid`` / ``plan`` / ``slot`` / ``active`` (paged modes) are
    loop constants: every layer dereferences the same batched page table."""
    pattern = cfg.layer_pattern
    aux0 = jnp.zeros((), jnp.float32)

    def body(carry, xs):
        x, aux = carry
        bp, bc = xs
        new_caches = {}
        for j, spec in enumerate(pattern):
            c_in = bc.get(f"p{j}") if bc is not None else None
            x, c_out, a = _apply_layer(bp[f"p{j}"], x, cfg, spec, flags, mode,
                                       c_in, pos, table, chunk_valid, plan,
                                       slot, active)
            aux = aux + a
            new_caches[f"p{j}"] = c_out
        ys = new_caches if mode != "train" else None
        return (x, aux), ys

    if flags.remat != "none" and mode == "train":
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if flags.remat == "dots" else
                  jax.checkpoint_policies.nothing_saveable)
        body = jax.checkpoint(body, policy=policy, prevent_cse=False)

    blocks_p = params["blocks"]
    blocks_c = cache["blocks"] if cache is not None else None

    if flags.unroll_layers:
        carry = (x, aux0)
        ys_list = []
        for i in range(cfg.num_pattern_blocks):
            bp = jax.tree.map(lambda a: a[i], blocks_p)
            bc = (jax.tree.map(lambda a: a[i], blocks_c)
                  if blocks_c is not None else None)
            carry, ys = body(carry, (bp, bc))
            ys_list.append(ys)
        (x, aux) = carry
        new_blocks_c = (jax.tree.map(lambda *a: jnp.stack(a), *ys_list)
                        if mode != "train" else None)
    else:
        (x, aux), new_blocks_c = jax.lax.scan(
            body, (x, aux0), (blocks_p, blocks_c))

    new_rem = {}
    for j, spec in enumerate(cfg.remainder_specs):
        c_in = cache["rem"].get(f"r{j}") if cache is not None else None
        apply = _apply_layer
        if flags.remat != "none" and mode == "train":
            # remainder layers need remat exactly like the scanned ones
            apply = jax.checkpoint(
                _apply_layer,
                policy=jax.checkpoint_policies.nothing_saveable,
                prevent_cse=False,
                static_argnums=(2, 3, 4, 5, 7))
        x, c_out, a = apply(params["rem"][f"r{j}"], x, cfg, spec, flags,
                            mode, c_in, pos, table, chunk_valid, plan, slot,
                            active)
        aux = aux + a
        new_rem[f"r{j}"] = c_out
    new_cache = (dict(blocks=new_blocks_c, rem=new_rem)
                 if mode != "train" else None)
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# embedding / logits / losses
# ---------------------------------------------------------------------------

def embed_tokens(params, cfg: ModelConfig, tokens: jax.Array) -> jax.Array:
    x = jnp.take(params["embed"]["tok"], tokens, axis=0)
    if cfg.normalize_embedding:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return x


def _head_weight(params):
    if "lm_head" in params:
        return params["lm_head"]
    return params["embed"]["tok"].T


def compute_logits(params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    logits = jnp.einsum("bsd,dv->bsv", x, _head_weight(params))
    return softcap(logits, cfg.final_logit_softcap)


def chunked_ce(params, cfg, x, labels, flags: RuntimeFlags) -> jax.Array:
    """Sequence-chunked CE so (B,S,V) logits are never materialized.
    ``loss_chunk=0`` computes single-shot (roofline mode: no inner scan)."""
    bsz, s, _ = x.shape
    if flags.loss_chunk <= 0:
        logits = compute_logits(params, cfg, x)
        logits = flags.shd(logits, ("batch", "seq", "vocab"))
        return cross_entropy(logits, labels)
    c = min(flags.loss_chunk, s)
    assert s % c == 0
    n = s // c
    xc = jnp.moveaxis(x.reshape(bsz, n, c, -1), 1, 0)
    lc = jnp.moveaxis(labels.reshape(bsz, n, c), 1, 0)

    @functools.partial(jax.checkpoint,
                       policy=jax.checkpoint_policies.nothing_saveable)
    def step(carry, xs):
        # checkpointed: without it the scan saves every (B, c, V) logits
        # chunk for backward, defeating the whole point of chunking.
        tot, cnt = carry
        xb, lb = xs
        logits = compute_logits(params, cfg, xb)
        logits = flags.shd(logits, ("batch", "seq", "vocab"))
        valid = (lb >= 0)
        safe = jnp.where(valid, lb, 0)
        lg = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(lg, axis=-1)
        ll = jnp.take_along_axis(lg, safe[..., None], axis=-1)[..., 0]
        tot = tot + jnp.sum((lse - ll) * valid)
        cnt = cnt + jnp.sum(valid)
        return (tot, cnt), None

    (tot, cnt), _ = jax.lax.scan(
        step, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)), (xc, lc))
    return tot / jnp.maximum(cnt, 1)


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

def forward(params, cfg: ModelConfig, flags: RuntimeFlags, tokens: jax.Array,
            patch_embeds: Optional[jax.Array] = None, mode: str = "train",
            cache: Optional[dict] = None, pos=None, table=None,
            chunk_valid=None, plan=None, slot=None, active=None):
    """tokens: (B, S_text); patch_embeds: (B, P, d) for vlm frontends.
    ``table``/``chunk_valid``/``plan``/``slot``/``active`` only apply to
    the paged modes."""
    x = embed_tokens(params, cfg, tokens)
    if patch_embeds is not None:
        x = jnp.concatenate([patch_embeds.astype(x.dtype), x], axis=1)
    x = flags.shd(x, ("batch", "seq", "embed"))
    x, new_cache, aux = _scan_blocks(params, x, cfg, flags, mode, cache, pos,
                                     table, chunk_valid, plan, slot, active)
    x = rms_norm(x, params["final_norm"])
    return x, new_cache, aux


def train_loss(params, cfg: ModelConfig, flags: RuntimeFlags, batch: dict):
    x, _, aux = forward(params, cfg, flags, batch["tokens"],
                        batch.get("patch_embeds"), mode="train")
    loss = chunked_ce(params, cfg, x, batch["labels"], flags)
    return loss + flags.aux_loss_weight * aux, dict(ce=loss, aux=aux)


def prefill(params, cfg: ModelConfig, flags: RuntimeFlags, batch: dict):
    """``batch["valid_len"]`` (scalar or (B,) int32, optional) marks the true
    prompt length when tokens are right-padded to a bucket (the serve fast
    path): last-token logits are read at ``valid_len - 1`` instead of the pad
    tail.  Causal attention keeps positions < valid_len exact under right
    padding; cache rows past valid_len are masked downstream by the decode
    step's ``kv_valid_len``."""
    x, cache, _ = forward(params, cfg, flags, batch["tokens"],
                          batch.get("patch_embeds"), mode="prefill")
    vl = batch.get("valid_len")
    if vl is None:
        last = x[:, -1:]
    else:
        bsz = x.shape[0]
        idx = jnp.broadcast_to(
            jnp.asarray(vl, jnp.int32).reshape(-1), (bsz,)) - 1
        last = x[jnp.arange(bsz), idx][:, None]
    last_logits = compute_logits(params, cfg, last)[:, 0]
    return cache, last_logits


def decode_step(params, cfg: ModelConfig, flags: RuntimeFlags, cache: dict,
                tokens: jax.Array, pos: jax.Array):
    """tokens: (B, 1); pos: scalar int32 (uniform across batch)."""
    x, new_cache, _ = forward(params, cfg, flags, tokens, mode="decode",
                              cache=cache, pos=pos)
    logits = compute_logits(params, cfg, x)[:, 0]
    return logits, new_cache


def paged_decode_step(params, cfg: ModelConfig, flags: RuntimeFlags,
                      cache: dict, tokens: jax.Array, pos: jax.Array,
                      table, plan=None, active=None):
    """One decode tick against the page pool.  tokens: (B, 1); pos: (B,)
    per-slot positions; table: ``{"full": (B, N), "ring": (B, R)}`` page
    tables (padded entries -> the null page; windowed layers read the ring
    table, full-attention layers the full one).  Every attention layer
    appends k/v through its table and dispatches the ``paged_attention``
    kernel under ``plan`` (the engine's tuned :class:`repro.tune.
    KernelPlan`; the kernel asserts the pool layout matches it and executes
    its pinned interpret mode); recurrent mixers advance dense per-slot
    state exactly like the dense decode path, except rows where ``active``
    (B,) is False keep their previous state — a pending-prefill slot's
    partial state must survive the masked ticks between its chunks."""
    x, new_cache, _ = forward(params, cfg, flags, tokens, mode="paged_decode",
                              cache=cache, pos=pos, table=table, plan=plan,
                              active=active)
    logits = compute_logits(params, cfg, x)[:, 0]
    return logits, new_cache


def paged_prefill_chunk(params, cfg: ModelConfig, flags: RuntimeFlags,
                        cache: dict, tokens: jax.Array, pos: jax.Array,
                        table, chunk_valid: jax.Array, slot=None):
    """One chunked-prefill step: ``tokens`` (B, C) is a prompt chunk
    (right-padded to a bucket; ``chunk_valid`` (B,) marks true length) at
    absolute context offset ``pos`` (B,).  Appends the chunk's k/v into the
    pages (full tables and rotating ring tables alike) and returns logits
    at the chunk's last valid position — only the final chunk's logits seed
    decoding.  ``slot`` is the engine slot whose dense recurrent state rows
    this chunk continues (hybrid stacks); the first chunk (``pos == 0``)
    restarts them from zeros."""
    x, new_cache, _ = forward(params, cfg, flags, tokens, mode="paged_extend",
                              cache=cache, pos=pos, table=table,
                              chunk_valid=chunk_valid, slot=slot)
    bsz = x.shape[0]
    idx = jnp.broadcast_to(
        jnp.asarray(chunk_valid, jnp.int32).reshape(-1), (bsz,)) - 1
    last = x[jnp.arange(bsz), idx][:, None]
    logits = compute_logits(params, cfg, last)[:, 0]
    return new_cache, logits


def paged_verify(params, cfg: ModelConfig, flags: RuntimeFlags, cache: dict,
                 tokens: jax.Array, pos: jax.Array, table,
                 chunk_valid: jax.Array, plan=None):
    """Speculative k-token verification: one batched ``paged_extend`` read.

    ``tokens`` (B, C) is ``[pending, draft_0 .. draft_{C-2}]`` per slot at
    absolute offset ``pos`` (B,); ``chunk_valid`` (B,) caps how many
    positions each slot may write (masked positions steer to the null
    page exactly like chunked prefill).  Unlike
    :func:`paged_prefill_chunk` this returns logits at EVERY position —
    (B, C, V) — because the acceptance rule needs the target distribution
    at each drafted offset, not just the last one.  Query position i
    attends rows ``<= pos + i`` (causal over the gathered page view), so
    row i's logits are bit-for-bit what ``paged_decode_step`` would have
    produced after emitting the same prefix — one page-table gather
    amortized over C positions instead of C serial single-token walks
    (the paper's burst-length lever applied to verification).  ``plan``
    is the engine's tuned verify-step :class:`repro.tune.KernelPlan`
    (``bq`` = verify width, ``bkv`` = the pool's page)."""
    x, new_cache, _ = forward(params, cfg, flags, tokens, mode="paged_extend",
                              cache=cache, pos=pos, table=table,
                              chunk_valid=chunk_valid, plan=plan)
    logits = compute_logits(params, cfg, x)
    return new_cache, logits
