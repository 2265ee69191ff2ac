"""Continuous-batching serving engine with a device-resident decode path.

Slot-based scheduler over a fixed decode batch: each slot holds one request
at its own position (the per-slot ``pos`` vector the decode step supports).
Two interchangeable KV backends:

- ``dense`` — the classic per-slot ``(batch, max_len)`` cache: prefill runs
  per-request into the slot's cache region, decode gathers dense rows.
- ``paged`` (default wherever the stack supports it) — vLLM-style
  continuous batching over a shared :class:`~repro.serve.kvcache.
  PageAllocator` pool: prefill appends k/v into fixed-size pages *in
  chunks* (a long prompt can no longer stall the decode tick), the decode
  fast path dispatches the ``paged_attention`` kernel against a
  device-resident ``(batch, max_pages)`` table, and finished requests
  release pages immediately — admission is bounded by live tokens, not
  ``batch x max_len``.  Common prompt prefixes share read-only pages
  (hash-chained prefix cache); pool exhaustion becomes backpressure
  (requests stay queued), never a crash.

The fast path is the paper's §5 pointer-chase fix applied to our own
scheduler: token selection — greedy argmax or full temperature/top-k/top-p
sampling (:class:`~repro.serve.sampling.SamplingParams`, per-slot PRNG
keys carried as device arrays) — is fused into the decode dispatch, tokens
and positions stay device arrays, and ``decode_many(n)`` runs n ticks
under one ``lax.fori_loop`` jit — one dispatch and one device->host
transfer (the token block) per *window*, not per token.  The page size
itself is a tuned knob: :func:`repro.tune.derive_paged_plan` derives it
from the advisor's ``unit_bytes >= 512B`` transaction-optimum rule, so
calibration reshapes the pool exactly the way it reshapes attention
blocks.

Each layer boundary of a round records a ``serve.*`` host span
(``jax.profiler.TraceAnnotation``: inert until a profiler session opens),
and each jitted program has a name of its own (``jit_decode_window``), so a
profile puts the host's work and the device's on one clock.

Speculative decoding (``draft_bundle``) rides the paged fast path: a
small draft model proposes ``spec_k`` tokens per dispatch from a dense
per-slot cache, the target verifies all of them in ONE batched
``paged_extend`` read over the page tables (``paged_verify`` — the
paper's burst-length lever: k+1 query positions amortize one table
walk), and rejected suffixes roll back page-table state
(:meth:`PageAllocator.truncate`) and per-slot keys.  Acceptance uses
*coupled* sampling: the target's sample at each position is drawn with
the same per-position subkey the vanilla fused loop would have used (one
split per emitted token), and a draft token is accepted only when it
equals that sample — so the emitted stream is bit-identical to the
non-speculative engine, greedy and sampled alike, and trivially
distribution-preserving.

The memory system is the product here — KV caches are the dominant HBM
consumer and the advisor classifies their access as the paper's `nest`
(prefill), `rs_tra` (dense decode streaming) and `r_acc` (paged table
indirection) patterns.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ATTN
from repro.core.memmodel import next_pow2
from repro.models.registry import ModelBundle
from repro.serve.hosttier import (HostKVEntry, HostKVTier, make_transfer_entry,
                                  page_axis)
from repro.serve.kvcache import (PageAllocator, PoolExhausted, PrefixIndex,
                                 page_hashes)
from repro.serve.sampling import (GREEDY, SamplingParams, sample_token,
                                  sample_tokens, split_keys, subkey_chain)
from repro.serve.scheduler import Scheduler, SwapCostModel, VictimInfo


@dataclass
class Request:
    rid: int
    prompt: np.ndarray               # (S,) int32
    max_new_tokens: int = 16
    priority: int = 0                # scheduler class: higher admits first
    deadline: Optional[int] = None   # cluster virtual-clock round to finish
                                     # by; None = no SLO (never shed)
    out_tokens: List[int] = field(default_factory=list)

    @property
    def done(self) -> bool:
        return len(self.out_tokens) >= self.max_new_tokens


@dataclass
class _Resume:
    """What a preempted request needs to pick up exactly where it left
    off.  ``ctx`` is the KV context (``prompt ++ out_tokens[:-1]``) whose
    rows the resume must restore — by re-prefilling it (``recompute``,
    prefix cache serving the surviving prompt pages) or by streaming the
    swapped pages back (``swap``; the page data lives in the host tier).
    ``pending`` is ``out_tokens[-1]``: the already-emitted token the next
    decode tick feeds, so resume must NOT re-seed from prefill logits."""

    kind: str                        # "swap" | "recompute"
    ctx: np.ndarray                  # (hpos,) int32
    pending: int


@dataclass
class ServeStats:
    prefills: int = 0                # requests fully prefilled
    decode_steps: int = 0            # device decode ticks executed
    tokens_out: int = 0
    decode_dispatches: int = 0       # fused decode_many launches (host syncs)
    prefill_retraces: int = 0        # distinct prefill shapes compiled
    # -- paged backend ----------------------------------------------------
    prefill_chunks: int = 0          # chunked-prefill dispatches
    prompt_tokens: int = 0           # prompt tokens admitted
    prefix_hit_tokens: int = 0       # prompt tokens served from shared pages
    pages_peak: int = 0              # peak full-pool pages_in_use over the run
    ring_pages_peak: int = 0         # peak ring-pool pages_in_use (windowed)
    pool_stalls: int = 0             # admissions deferred by PoolExhausted
    kv_pages_live: int = 0           # table entries the decode kernel read
    kv_pages_table: int = 0          # table entries it could read (B x N)
    # -- speculative decoding ---------------------------------------------
    spec_steps: int = 0              # draft->verify dispatches
    draft_tokens: int = 0            # draft tokens proposed to the verifier
    draft_accepted: int = 0          # proposals matching the coupled sample
    # -- scheduler / preemption ---------------------------------------------
    preemptions: int = 0             # mid-flight evictions (all modes)
    preempt_restarts: int = 0        # mid-prefill victims requeued from scratch
    swap_outs: int = 0               # victims whose pages moved to the host tier
    swap_ins: int = 0                # resumes streamed back through the table
    swap_bytes: int = 0              # bytes moved across the host tier, both ways
    recompute_resumes: int = 0       # resumes that re-prefilled their context
    swap_fallbacks: int = 0          # checksum-failed swaps recovered by recompute
    prefill_burst_max: int = 0       # max prefill chunks between decode windows
    # -- disaggregated prefill/decode ---------------------------------------
    prefill_exports: int = 0         # finished prefills shipped off this engine
    prefill_imports: int = 0         # shipped prefills landed into decode slots
    transfer_bytes: int = 0          # bytes crossing the prefill->decode link
    transfer_fallbacks: int = 0      # corrupted transfers recovered by recompute

    @property
    def kv_live_page_share(self) -> float:
        """Share of the page tables the decode kernel read: it copies only
        the pages that hold an active slot's live tokens (a ring table's
        every slot), summed over ticks, slots and tables."""
        return self.kv_pages_live / max(1, self.kv_pages_table)

    @property
    def accept_rate(self) -> float:
        """Fraction of proposed draft tokens the target accepted."""
        return self.draft_accepted / max(1, self.draft_tokens)

    @property
    def accepted_per_step(self) -> float:
        """Mean accepted draft tokens per verify dispatch: the speedup
        knob — every accepted token is a serial target pass amortized
        into the batched verify read."""
        return self.draft_accepted / max(1, self.spec_steps)


class ServeEngine:
    """Continuous-batching engine; batch-uniform architecture state handled
    per family.  Token selection is fused on device: greedy argmax by
    default, or temperature/top-k/top-p sampling via ``sampling`` with
    per-slot PRNG keys derived as ``fold_in(PRNGKey(seed), rid)`` — a
    slot's stream depends only on the request, never on scheduling, and
    masked/pending/budget-exhausted slots consume no PRNG state.

    ``window`` is the fused decode chunk: ``run_to_completion`` advances all
    active slots up to ``window`` tokens per dispatch.  ``bucket_prompts``
    pads prompts (dense) / prefill chunks (paged) to the next power of two
    (defaults to on for pure full-attention decoders, where right-padding
    is provably masked; recurrent/windowed/enc-dec families keep exact
    lengths).  ``cache_backend`` is ``"dense"``, ``"paged"``, or ``None``
    (auto: paged wherever :meth:`ModelBundle.paged_supported` allows).

    Paged knobs: ``page_size=None`` derives from the tuned
    :class:`~repro.tune.KernelPlan` (int8 KV halves the unit size, so the
    derived page doubles in tokens); ``num_pages=None`` sizes the
    full-attention pool at the dense footprint plus the reserved null page
    — shrink it to admit by live tokens and exercise backpressure, grow it
    to persist more prefix cache.  ``num_ring_pages=None`` sizes the
    windowed-layer ring pool at ``batch x (ceil(window/page)+1)`` rotating
    pages — the constant-memory bound however long windowed sequences run.
    ``prefill_chunk`` caps prompt tokens per prefill dispatch so decode
    ticks interleave with long prompts.

    Speculative decoding: pass ``draft_bundle``/``draft_params`` (a small
    pure full-attention decoder sharing the target's vocab) and the paged
    engine switches ``decode_many`` to draft->verify dispatches of up to
    ``spec_k`` proposed tokens each.  The emitted stream is bit-identical
    to the non-speculative engine (coupled-sample verification), so the
    draft only changes *throughput*, never output.  Requires a pure
    full-attention target stack: ring rotation and recurrent state cannot
    roll back a rejected suffix.

    Tensor parallelism: pass ``dist`` (a :class:`repro.dist.ServeMesh`)
    and this ONE engine spans the mesh — params shard by the ``tp``
    policy, the KV page pools split on their kv-heads dim (every shard
    holds its head-stripe of every page; one global page-id space, tables
    replicated), and the paged dispatches run as ``shard_map`` islands.
    Logits are all-gathered before token selection, so a TP=N drain is
    token-identical to the single-device engine — greedy, sampled, and
    speculative.  Requires ``cache_backend="paged"`` and ``tp`` dividing
    both head counts.  DP is a scheduling concern, not an engine one:
    see ``launch/serve.py:ReplicaPool``.
    """

    def __init__(self, bundle: ModelBundle, params, batch_size: int,
                 max_len: int, *, window: int = 8,
                 bucket_prompts: Optional[bool] = None,
                 cache_backend: Optional[str] = None,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 num_ring_pages: Optional[int] = None,
                 prefill_chunk: int = 32,
                 prefix_cache: bool = True,
                 sampling: Optional[SamplingParams] = None,
                 seed: int = 0,
                 draft_bundle: Optional[ModelBundle] = None,
                 draft_params=None,
                 spec_k: int = 4,
                 dist=None,
                 scheduler: Optional[Scheduler] = None,
                 host_tier: Optional[HostKVTier] = None):
        self.bundle = bundle
        self.params = params
        self.bsz = batch_size
        self.max_len = max_len
        self.window = max(1, window)
        self.sampling = sampling or GREEDY
        self.seed = seed
        self._base_key = jax.random.PRNGKey(seed)
        self.draft = draft_bundle
        self.draft_params = draft_params
        self.spec_k = max(1, spec_k)
        if cache_backend is None:
            cache_backend = "paged" if bundle.paged_supported() else "dense"
        elif cache_backend not in ("dense", "paged"):
            raise ValueError(f"unknown cache_backend {cache_backend!r}")
        elif cache_backend == "paged" and not bundle.paged_supported():
            raise ValueError(
                f"{bundle.cfg.name}: paged KV serves decoder-only stacks "
                "(enc-dec and frontend stacks keep the dense cache; see "
                "ModelBundle.paged_supported)")
        self.backend = cache_backend
        # -- tensor parallelism (dist = a repro.dist.ServeMesh) ------------
        # one engine spans the mesh: params shard by the tp policy, the KV
        # page pools split on their kv-heads dim, page tables + sampling
        # state replicate, and the paged dispatches run as shard_map
        # islands.  The host-side allocator keeps ONE global page-id space,
        # so every bit of scheduling below is mesh-oblivious.
        self.dist = dist
        self.tp = 1
        if dist is not None:
            if self.backend != "paged":
                raise ValueError(
                    "dist serving shards the KV page pools; "
                    "cache_backend='paged' is required")
            dist.validate(bundle.cfg)
            bundle = self.bundle = dist.bind(bundle)
            params = self.params = dist.shard_params(bundle, params)
            if draft_bundle is not None:
                dist.validate(draft_bundle.cfg)
                draft_bundle = self.draft = dist.bind(draft_bundle)
                if draft_params is not None:
                    draft_params = self.draft_params = dist.shard_params(
                        draft_bundle, draft_params)
            self.tp = dist.tp_degree
        self.bucket_prompts = (self._bucketable(bundle.cfg)
                               if bucket_prompts is None else bucket_prompts)

        if self.backend == "paged":
            cfg = bundle.cfg
            specs = tuple(cfg.layer_pattern) + tuple(cfg.remainder_specs)
            attn = [s for s in specs if s.mixer == ATTN]
            self.has_full = any(s.sliding_window is None for s in attn)
            windows = [s.sliding_window for s in attn
                       if s.sliding_window is not None]
            # the ring is sized by the largest window (smaller windows mask
            # more); a window past max_len degenerates to hold-everything
            self.attn_window = (min(max(windows), max_len)
                                if windows else None)
            self.has_recurrent = any(s.mixer != ATTN for s in specs)
            hd = cfg.resolved_head_dim
            from repro.tune import plan_for
            # int8 pages halve the unit size, so the transaction-optimum
            # page (the r_acc >= 512B rule) doubles in tokens — derive the
            # plan from the dtype the pool actually stores
            kv_store = ("int8" if bundle.flags.kv_dtype == "int8"
                        else str(cfg.compute_dtype))
            # under TP the plan cache keys by the PER-SHARD kv-head count:
            # each shard's kernel walks its own pool slice, so a calibrated
            # multi-device host derives its plan independently of the
            # single-device one (page geometry itself is per-head-row and
            # does not change)
            sig = ((max_len, hd) if self.tp == 1
                   else (max_len, hd, cfg.num_kv_heads // self.tp))
            base = plan_for("paged_attention", shape_sig=sig, dtype=kv_store)
            self.page = int(page_size or base.page_size)
            # an explicit page_size overrides the derived one; the plan the
            # kernel receives must describe the pool actually laid out
            self.plan = (base if base.page_size == self.page
                         else dataclasses.replace(base, bkv=self.page))
            self.pages_per_seq = (-(-max_len // self.page)
                                  if self.has_full else 0)
            self.ring_slots = (-(-self.attn_window // self.page) + 1
                               if self.attn_window is not None else 0)
            # dense-footprint default + the reserved null page (id 0) that
            # padded table entries target, so masked writes stay harmless
            self.num_pages = int(num_pages
                                 or 1 + batch_size * self.pages_per_seq)
            self.num_ring_pages = int(num_ring_pages
                                      or 1 + batch_size * self.ring_slots)
            self.prefill_chunk = max(8, prefill_chunk)
            # empty pools come from one jitted program; under TP its
            # outputs land directly in the per-shard slices (same page ids
            # on every shard, each holding its own kv-heads stripe of every
            # page), so no pool is ever whole on one device
            make = _named(
                "new_page_pools", bundle.init_paged_cache,
                self.num_pages if self.has_full else 1, self.page,
                batch=batch_size, ring_pages=self.num_ring_pages)
            self._new_pools = jax.jit(make, out_shardings=(
                None if dist is None
                else dist.paged_cache_shardings(jax.eval_shape(make))))
            # prefix pages are only reusable when the WHOLE stack reads
            # them: ring layers rotate prefix tokens away and recurrent
            # state is never cached, so sharing is a pure-full-attn move
            pure_full = self.has_full and not windows and not self.has_recurrent
            self.prefix: Optional[PrefixIndex] = (
                PrefixIndex() if prefix_cache and pure_full else None)
            def _prefill_impl(p, cache, toks, off, tbl, cv, slot,
                              bundle=bundle):
                cache, logits = bundle.paged_prefill_chunk(
                    p, cache, toks, off, tbl, cv, slot)
                return cache, _gather_logits(bundle, logits)

            self._paged_prefill = jax.jit(_prefill_impl, donate_argnums=(1,))
            self._paged_decode_many = jax.jit(
                _named("decode_window", _paged_decode_many_impl, bundle,
                       self.plan, self.sampling),
                static_argnums=(0,), donate_argnums=(2,))
        else:
            def prefill(p, toks, vl):
                return bundle.prefill(p, dict(tokens=toks, valid_len=vl))

            self._prefill = jax.jit(prefill)
            self._decode_many = jax.jit(
                _named("decode_window", _decode_many_impl, bundle,
                       self.sampling),
                static_argnums=(0,), donate_argnums=(2,))
        if draft_bundle is not None:
            self._init_spec(draft_bundle)
        # -- scheduler: priority admission + mid-flight preemption ---------
        # swap-resume needs whole-page state capture, which only pure
        # full-attention stacks offer (ring rotation and recurrent state
        # are not in the full pool); everything else resumes by recompute.
        self.sched = scheduler or Scheduler()
        self._swappable = (self.backend == "paged" and self.has_full
                           and self.attn_window is None
                           and not self.has_recurrent)
        self.host_tier: Optional[HostKVTier] = None
        if self._swappable and self.sched.config.swap:
            self.host_tier = host_tier or HostKVTier()
        self._seen_prefill_shapes = set()
        self._init_state()
        if self.host_tier is not None:
            self._gather_pages = jax.jit(_gather_pages_impl)
            # pin the scatter's output sharding under TP so a swap-in
            # cannot silently replicate the pools
            if self.dist is None:
                self._scatter_pages = jax.jit(_scatter_pages_impl,
                                              donate_argnums=(0,))
            else:
                self._scatter_pages = jax.jit(
                    _scatter_pages_impl, donate_argnums=(0,),
                    out_shardings=self.dist.page_swap_shardings(self.cache))

    def _init_spec(self, draft: ModelBundle) -> None:
        """Validate + compile the speculative draft->verify dispatch."""
        cfg = self.bundle.cfg
        if self.draft_params is None:
            raise ValueError("draft_bundle needs draft_params")
        if self.backend != "paged":
            raise ValueError(
                "speculative decoding rides the paged fast path; "
                "cache_backend='paged' is required")
        if not (self.has_full and self.attn_window is None
                and not self.has_recurrent):
            raise ValueError(
                f"{cfg.name}: speculative verify needs suffix rollback, "
                "which only pure full-attention page tables support (ring "
                "rotation overwrites history and recurrent state cannot "
                "rewind)")
        if draft.cfg.vocab_size != cfg.vocab_size:
            raise ValueError(
                f"draft vocab {draft.cfg.vocab_size} != target vocab "
                f"{cfg.vocab_size}: proposals must share the token space")
        if not self._bucketable(draft.cfg):
            raise ValueError(
                f"{draft.cfg.name}: the draft must be a pure full-attention "
                "decoder — its rollback is a position rewind over a dense "
                "cache, which windows/recurrence cannot mask")
        from repro.tune import plan_for
        kv_store = ("int8" if self.bundle.flags.kv_dtype == "int8"
                    else str(cfg.compute_dtype))
        vsig = (self.spec_k + 1, self.max_len, cfg.resolved_head_dim)
        if self.tp > 1:  # keyed per shard, like the decode plan
            vsig += (cfg.num_kv_heads // self.tp,)
        vplan = plan_for("paged_verify", shape_sig=vsig, dtype=kv_store)
        # the verify step reads the pool the engine laid out: an explicit
        # page_size override must reach the verify plan too
        self.vplan = (vplan if vplan.page_size == self.page
                      else dataclasses.replace(vplan, bkv=self.page))
        def draft_prefill(p, toks, vl):
            return self.draft.prefill(p, dict(tokens=toks, valid_len=vl))

        self._draft_prefill = jax.jit(draft_prefill)
        self._spec_decode = jax.jit(
            _named("spec_decode_window", _spec_decode_many_impl, self.bundle,
                   self.draft, self.vplan, self.sampling, self.spec_k),
            donate_argnums=(2, 3))

    def _init_state(self) -> None:
        self.pos = self._dev(jnp.zeros((self.bsz,), jnp.int32))
        self.tokens = self._dev(jnp.zeros((self.bsz, 1), jnp.int32))
        # per-slot PRNG keys (device): set at admission from (seed, rid),
        # advanced one split per emitted token inside the fused loops.
        # Under TP they replicate across the mesh — token selection runs on
        # all-gathered logits, so every shard walks the same chain
        self.keys = self._dev(jnp.zeros((self.bsz, 2), jnp.uint32))
        self._hpos = np.zeros((self.bsz,), np.int64)       # host mirror
        if self.draft is not None:
            self.draft_cache = self._dev(
                self.draft.init_cache(self.bsz, self.max_len))
        self.slots: List[Optional[Request]] = [None] * self.bsz
        self.queue: List[Request] = []
        self.stats = ServeStats()
        # scheduler state: resume records for preempted requests, arrival
        # sequence (priority ties admit FIFO), and the chunks-since-decode
        # counter behind stats.prefill_burst_max
        self._resume: Dict[int, _Resume] = {}
        self._arrival: Dict[int, int] = {}
        self._arrival_seq = 0
        self._chunks_since_decode = 0
        # rids whose pending swap-resume is a cross-mesh prefill import
        # (counts against the transfer stats, not the local swap stats)
        self._transfer_rids: set = set()
        if self.host_tier is not None:
            self.host_tier.clear()
        if self.backend == "paged":
            self.alloc = (PageAllocator(self.num_pages, self.page, reserved=1)
                          if self.has_full else None)
            self.ralloc = (PageAllocator(self.num_ring_pages, self.page,
                                         reserved=1, window=self.attn_window)
                           if self.attn_window is not None else None)
            if self.prefix is not None:
                self.prefix = PrefixIndex()
            self.cache = self._new_pools()
            self._htable = np.zeros((self.bsz, max(1, self.pages_per_seq)),
                                    np.int32)
            self._hrtable = np.zeros((self.bsz, max(1, self.ring_slots)),
                                     np.int32)
            self._sync_table()
            self._pending: Dict[int, int] = {}   # slot -> next prefill offset
            self._hashes: Dict[int, List[str]] = {}  # rid -> full-page hashes
        else:
            self.cache = self.bundle.init_cache(self.bsz, self.max_len)

    def reset(self) -> None:
        """Clear all serving state (cache, pool, slots, queue, stats —
        including the speculative accept-rate counters and the per-slot
        PRNG keys, both rebuilt from scratch in ``_init_state`` — plus
        resume records and the host swap tier) but KEEP the compiled
        prefill/decode callables and their trace caches — benchmark
        drivers drain once to warm the jit caches, reset, then time a
        steady-state drain.  A warm drain after a preempted one therefore
        starts with zeroed accept-rate stats and virgin key state."""
        self._init_state()
        # _seen_prefill_shapes survives: those shapes remain compiled, so a
        # post-reset drain reports only genuinely new compiles

    def _dev(self, x):
        """Place host/engine state on the mesh (replicated) under TP; a
        no-op single-device."""
        return x if self.dist is None else self.dist.replicated(x)

    def _sync_table(self) -> None:
        """Publish the host table mirrors as the device table dict (page
        tables replicate across the mesh — page ids are global)."""
        self._table = dict(full=self._dev(jnp.asarray(self._htable)),
                           ring=self._dev(jnp.asarray(self._hrtable)))
        self._table_dirty = False

    @staticmethod
    def _bucketable(cfg) -> bool:
        """Right-padding is mask-safe only when every mixer is full causal
        attention: windowed ring caches would evict real tokens for pad, and
        recurrent state (ssd/rglru) would absorb the pad tokens."""
        if cfg.enc_dec or cfg.frontend:
            return False
        specs = tuple(cfg.layer_pattern) + tuple(cfg.remainder_specs)
        return all(s.mixer == ATTN and s.sliding_window is None
                   for s in specs)

    # ------------------------------------------------------------------
    # bookkeeping views (benchmarks / examples)
    # ------------------------------------------------------------------
    def kv_bytes(self) -> int:
        """Allocated HBM bytes of the KV cache pytree (both backends)."""
        return int(sum(x.size * x.dtype.itemsize
                       for x in jax.tree_util.tree_leaves(self.cache)))

    def _page_bytes_by_kind(self, per_shard: bool = False):
        """(full, ring) HBM bytes of ONE page summed over every layer of
        that kind (k + v, plus the int8 scale lanes).  ``per_shard``
        reports one TP shard's slice: the pools split on kv-heads, so page
        bytes divide by ``tp``; the scale lanes replicate (they are
        per-token, reduced over heads) and do not."""
        cfg = self.bundle.cfg
        nb = cfg.num_pattern_blocks
        n_full = n_ring = 0
        for spec, mult in ([(s, nb) for s in cfg.layer_pattern]
                           + [(s, 1) for s in cfg.remainder_specs]):
            if spec.mixer != ATTN:
                continue
            if spec.sliding_window is None:
                n_full += mult
            else:
                n_ring += mult
        int8 = self.bundle.flags.kv_dtype == "int8"
        itm = 1 if int8 else jnp.dtype(cfg.compute_dtype).itemsize
        heads = cfg.num_kv_heads // (self.tp if per_shard else 1)
        per_layer = (2 * self.page * heads
                     * cfg.resolved_head_dim * itm
                     + (2 * self.page * 4 if int8 else 0))
        return n_full * per_layer, n_ring * per_layer

    @property
    def bytes_per_page(self) -> int:
        """One page across every layer pool of its kind (k + v)."""
        assert self.backend == "paged"
        full_pb, ring_pb = self._page_bytes_by_kind()
        return full_pb or ring_pb

    def _recurrent_state_bytes(self) -> int:
        """Dense per-slot recurrent state (hybrid stacks): always live."""
        full_pb, ring_pb = self._page_bytes_by_kind()
        pools = ((self.num_pages * full_pb if self.has_full else 0)
                 + (self.num_ring_pages * ring_pb if self.ralloc else 0))
        return self.kv_bytes() - pools

    def live_kv_bytes_peak(self, per_shard: bool = False) -> int:
        """Peak *live-token* HBM bytes: what the cache actually held, vs the
        ``batch x max_len`` footprint the dense backend commits upfront.
        Ring layers are the headline win: however long a windowed sequence
        runs, its pages stay bounded by ``ceil(window/page)+1``.
        ``per_shard`` reports one TP shard's slice (pool bytes divide by
        the mesh width; replicated recurrent state does not) — the
        per-channel footprint in the paper's multi-bank framing."""
        if self.backend == "paged":
            full_pb, ring_pb = self._page_bytes_by_kind(per_shard)
            return (self.stats.pages_peak * full_pb
                    + self.stats.ring_pages_peak * ring_pb
                    + self._recurrent_state_bytes())
        return self.kv_bytes()

    # ------------------------------------------------------------------
    def add_request(self, req: Request):
        if req.rid not in self._arrival:
            self._arrival[req.rid] = self._arrival_seq
            self._arrival_seq += 1
        self.queue.append(req)

    def adopt(self, req: Request) -> None:
        """Admit a request that may already be mid-stream — the cluster
        failover path.  A request evacuated from another replica carries
        its emitted tokens on the host-side :class:`Request`; adoption
        installs the recompute-resume record an in-engine preemption
        would have left (re-prefill ``prompt ++ emitted[:-1]``, re-feed
        — never re-sample — the pending last token, replay the
        ``(seed, rid)`` PRNG chain past the emitted prefix).  Because
        that chain depends only on the request and the engine seed, a
        drain finished *here* is bitwise the one the failed replica
        would have produced.  Fresh requests fall through to plain
        :meth:`add_request`."""
        if req.done:
            # already at its token budget: there is nothing left to run —
            # adopting it into a slot would re-prefill a finished request.
            # The caller keeps the (complete) Request object; no-op here.
            return
        if req.out_tokens:
            ctx = np.concatenate(
                [np.asarray(req.prompt, np.int32),
                 np.asarray(req.out_tokens[:-1], np.int32)])
            self._resume[req.rid] = _Resume("recompute", ctx,
                                            int(req.out_tokens[-1]))
        self.add_request(req)

    def evacuate(self) -> List[Request]:
        """Pull every unfinished request off this engine — queued AND
        in-flight — for adoption by another replica (cluster failover
        after a crash or quarantine).  In-flight slots preempt in
        ``recompute`` mode; the resume records and host-tier entries this
        engine held are *dropped*, because no device or host-tier state
        can follow a request across replicas — :meth:`adopt` re-derives
        resume state from the request alone.  Finished slots retire
        normally.  The engine is left idle with every per-request page
        released (prefix-pinned pages persist until the router decides
        the HBM itself is gone)."""
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            if req.done:
                self._release_finished(i)
            else:
                self.preempt(i, mode="recompute")
        moved = list(self.queue)
        self.queue.clear()
        for r in moved:
            res = self._resume.pop(r.rid, None)
            if (res is not None and res.kind == "swap"
                    and self.host_tier is not None
                    and r.rid in self.host_tier):
                self.host_tier.pop(r.rid)
            self._transfer_rids.discard(r.rid)
            self._arrival.pop(r.rid, None)
        return moved

    # ------------------------------------------------------------------
    # disaggregated prefill/decode: finished-prefill hand-off
    # ------------------------------------------------------------------
    def export_finished_prefill(self, slot: int):
        """Ship a freshly prefilled request off this engine: gather its
        pages (k/v + int8 scale lanes; per-shard stripes assembled on host
        under TP) into a checksummed transfer buffer, release every local
        resource, and return ``(request, entry)`` for a decode mesh to
        :meth:`import_prefill`.

        Mechanically this is a swap-out of a request that has emitted
        exactly its seed token — the prefill side's last act.  The pending
        token rides on ``request.out_tokens``; the PRNG chain needs no
        shipping because it is a pure function of ``(seed, rid)`` and the
        emitted count.  Requires the host swap tier (paged, pure
        full-attention stack) and a completed prefill."""
        req = self.slots[slot]
        if req is None:
            raise ValueError(f"export of empty slot {slot}")
        if not self._swap_ok():
            raise ValueError(
                "export requires the host swap tier (paged backend, pure "
                "full-attention stack, scheduler swap enabled)")
        if slot in self._pending:
            raise ValueError(
                f"slot {slot} is mid-prefill: only a completed prefill "
                "(seed token emitted) can be exported")
        if len(req.out_tokens) != 1:
            raise ValueError(
                f"rid {req.rid} has emitted {len(req.out_tokens)} tokens; "
                "export is a prefill hand-off — decode must not have begun")
        hpos = int(self._hpos[slot])
        # drop any reservation past the live rows, then gather the table
        # (shared prefix pages are read-only; gathering them is safe)
        self.alloc.truncate(req.rid, hpos)
        pids = list(self.alloc.tables[req.rid])
        data = self._gather_to_host(pids)
        entry = make_transfer_entry(req.rid, data, len(pids), length=hpos)
        self.stats.prefill_exports += 1
        self.stats.transfer_bytes += entry.nbytes
        self.alloc.release(req.rid)
        if self.ralloc is not None:
            self.ralloc.release(req.rid)
        self._hashes.pop(req.rid, None)
        self._htable[slot, :] = 0
        self._hrtable[slot, :] = 0
        self._table_dirty = True
        self.slots[slot] = None
        self._arrival.pop(req.rid, None)
        return req, entry

    def import_prefill(self, req: Request, entry: HostKVEntry) -> None:
        """Land a shipped prefill on this (decode) engine: install the
        transfer buffer in the local host tier VERBATIM — original
        checksum and all — and queue the request behind a swap-kind resume
        record.  Admission then walks the ordinary swap-in path: reserve
        pages, scatter the buffer through the page table, restore
        pos/pending-token, replay the ``(seed, rid)`` PRNG chain.  A
        checksum mismatch (corruption anywhere in transit) degrades to
        recompute-resume: the prompt re-prefills *here*, chunked, which is
        bitwise the same stream — the transfer is an optimization, never a
        correctness dependency."""
        if not self._swap_ok():
            raise ValueError(
                "import requires the host swap tier on the decode engine "
                "(paged backend, pure full-attention stack, swap enabled)")
        if len(req.out_tokens) != 1:
            raise ValueError(
                f"rid {req.rid} has emitted {len(req.out_tokens)} tokens; "
                "import expects a prefill hand-off (exactly the seed token)")
        ctx = np.asarray(req.prompt, np.int32)
        if int(entry.length) != len(ctx):
            raise ValueError(
                f"transfer entry covers {entry.length} rows but rid "
                f"{req.rid}'s prompt holds {len(ctx)} tokens")
        self.host_tier.put_entry(entry)
        self._transfer_rids.add(req.rid)
        self._resume[req.rid] = _Resume("swap", ctx, int(req.out_tokens[-1]))
        self.add_request(req)

    def _free_slot(self) -> Optional[int]:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return None

    # ------------------------------------------------------------------
    # sampling state
    # ------------------------------------------------------------------
    def _assign_key(self, slot: int, req: Request) -> None:
        """Pin the slot's PRNG stream to the request: the key depends only
        on ``(seed, rid)``, never on which slot the request landed in or
        what ran there before — replays are churn-invariant."""
        if self.sampling.greedy:
            return  # greedy never touches PRNG state
        self.keys = self.keys.at[slot].set(
            jax.random.fold_in(self._base_key, req.rid))

    def _seed_token(self, slot: int, logits_row) -> int:
        """First decode token from the prefill logits, drawn with the same
        one-split-per-token chain the fused loop continues."""
        if self.sampling.greedy:
            return int(np.argmax(np.asarray(logits_row)))
        nk, sub = jax.random.split(self.keys[slot])
        tok = int(sample_token(sub, jnp.asarray(logits_row), self.sampling))
        self.keys = self.keys.at[slot].set(nk)
        return tok

    def _replay_key(self, slot: int, req: Request) -> None:
        """Restore the slot's PRNG chain after a resume: re-derive the
        admission key from ``(seed, rid)`` and advance it one split per
        token the request has already emitted — the carried key is then
        bitwise the one an unpreempted run would hold, so the continued
        stream (sampled or speculative) cannot diverge."""
        if self.sampling.greedy:
            return  # greedy consumes zero PRNG state
        n = len(req.out_tokens)
        base = jax.random.fold_in(self._base_key, req.rid)
        if n:
            _, carried = subkey_chain(base[None], n)
            base = carried[0, n]
        self.keys = self.keys.at[slot].set(base)

    # ------------------------------------------------------------------
    # preemption: victim choice, page swap, resume
    # ------------------------------------------------------------------
    def _cost_model(self) -> SwapCostModel:
        """The scheduler's swap-vs-recompute pricer, lazily derived from
        this engine's own geometry when the caller didn't inject a
        calibrated one: weight bytes (each prefill chunk re-streams them)
        and KV bytes per token (what a swap moves per context row)."""
        if self.sched.cost_model is None:
            wb = sum(x.size * x.dtype.itemsize
                     for x in jax.tree_util.tree_leaves(self.params))
            if self.backend == "paged":
                kv_tok = self.bytes_per_page / self.page
                chunk = self.prefill_chunk
            else:
                kv_tok = self.kv_bytes() / (self.bsz * self.max_len)
                chunk = self.max_len  # dense prefill is one dispatch
            self.sched.cost_model = SwapCostModel(
                weight_bytes=wb, kv_bytes_per_token=kv_tok,
                prefill_chunk=chunk,
                host_link_bw=self.sched.config.host_link_bw)
        return self.sched.cost_model

    def _swap_ok(self) -> bool:
        return self.host_tier is not None

    def _victims(self, exclude=()) -> List[VictimInfo]:
        """Preemption candidacies of every active slot, as the scheduler's
        policy sees them.  Mid-prefill slots count the tokens already
        chunked in as their recompute cost (a restart redoes them)."""
        cands = []
        for i, req in enumerate(self.slots):
            if req is None or i in exclude or req.done:
                continue
            pages = 0
            if self.backend == "paged":
                for a in (self.alloc, self.ralloc):
                    if a is not None:
                        pages += len(a.tables.get(req.rid, ()))
                ctx = (self._pending[i] if i in self._pending
                       else int(self._hpos[i]))
            else:
                ctx = int(self._hpos[i])
            # swappability is per victim: the engine must hold a host tier
            # (paged, pure full attention — ring/hybrid stacks never do),
            # and a mid-prefill slot can only restart, never swap
            cands.append(VictimInfo(slot=i, rid=req.rid, priority=req.priority,
                                    ctx_tokens=ctx, pages=pages,
                                    swappable=(self._swap_ok()
                                               and i not in self._pending)))
        return cands

    def _pick_victim(self, below: Optional[int] = None) -> Optional[int]:
        v = self.sched.pick_victim(self._victims(), below=below)
        if v is None:
            return None
        self._cost_model()  # materialize before preempt() prices the resume
        return v.slot

    def preempt(self, slot: int, mode: Optional[str] = None) -> str:
        """Evict the request in ``slot`` mid-flight and requeue it.

        Returns the eviction mode used: ``"restart"`` (mid-prefill — the
        partial pages are dropped and the prompt re-admits from scratch,
        minus whatever the prefix cache retained), ``"recompute"`` (the
        resume re-prefills ``prompt ++ emitted[:-1]``), or ``"swap"`` (the
        pages moved to the host tier and stream back on resume).  ``mode``
        forces the choice; default defers to the scheduler's cost model.
        Either way the resumed request drains token-identically to an
        unpreempted run: KV rows are restored exactly (swap) or recomputed
        row-for-row (chunked prefill is position-wise), the pending token
        is re-fed rather than re-sampled, and the PRNG chain is replayed
        to the carried key."""
        req = self.slots[slot]
        if req is None:
            raise ValueError(f"preempt of empty slot {slot}")
        self.stats.preemptions += 1
        if self.backend == "paged" and slot in self._pending:
            # prompt still building: nothing emitted, no resume state —
            # drop the partial pages and let admission redo the prompt
            del self._pending[slot]
            self._hashes.pop(req.rid, None)
            if self.alloc is not None:
                self.alloc.release(req.rid)
            if self.ralloc is not None:
                self.ralloc.release(req.rid)
            self.slots[slot] = None
            self._htable[slot, :] = 0
            self._hrtable[slot, :] = 0
            self._table_dirty = True
            self.stats.preempt_restarts += 1
            self.queue.append(req)
            return "restart"
        hpos = int(self._hpos[slot])
        ctx = np.concatenate(
            [np.asarray(req.prompt, np.int32),
             np.asarray(req.out_tokens[:-1], np.int32)])
        assert len(ctx) == hpos, "context/KV length drift"
        if mode is None:
            mode = self._cost_model().choose(hpos, self._swap_ok())
        elif mode == "swap" and not self._swap_ok():
            mode = "recompute"
        if mode == "swap":
            # capture exactly the live rows: drop window-reservation pages
            # past hpos first, then gather the table (shared prefix pages
            # are read-only — gathering them is safe, and resume owns
            # private copies)
            self.alloc.truncate(req.rid, hpos)
            pids = list(self.alloc.tables[req.rid])
            data = self._gather_to_host(pids)
            entry = self.host_tier.put(req.rid, data, len(pids), length=hpos)
            self.stats.swap_outs += 1
            self.stats.swap_bytes += entry.nbytes
        self._resume[req.rid] = _Resume(mode, ctx, int(req.out_tokens[-1]))
        if self.backend == "paged":
            if self.alloc is not None:
                self.alloc.release(req.rid)
            if self.ralloc is not None:
                self.ralloc.release(req.rid)
            self._hashes.pop(req.rid, None)
            self._htable[slot, :] = 0
            self._hrtable[slot, :] = 0
            self._table_dirty = True
        self.slots[slot] = None
        self.queue.append(req)
        return mode

    def _gather_to_host(self, pids: List[int]):
        """Device->host page gather: one fused take over every pool leaf
        (k/v pages + int8 scale lanes), page list padded to a power of two
        with null-page ids (bounded trace count; the null page's junk is
        outside the checksummed span).  Under TP each shard gathers its
        own kv-heads stripe and ``device_get`` assembles the full pages on
        host — the per-shard half of the disaggregation primitive."""
        m = next_pow2(max(1, len(pids)))
        idx = jnp.asarray(list(pids) + [0] * (m - len(pids)), jnp.int32)
        return jax.device_get(self._gather_pages(self.cache, self._dev(idx)))

    def _swap_in_slot(self, slot: int, req: Request, res: _Resume) -> bool:
        """Stream a swapped-out request's pages back through the page
        table: reserve fresh pages (ids may differ — the table indirection
        is what makes that free), scatter the host bytes, republish the
        row, and restore pos/pending-token/PRNG state.  False when the
        checksum no longer matches: the entry is dropped and the caller
        degrades to recompute-resume (chaos-injected corruption lands
        here)."""
        entry, ok = self.host_tier.get(req.rid)
        if not ok:
            self.host_tier.pop(req.rid)
            if req.rid in self._transfer_rids:
                self._transfer_rids.discard(req.rid)
                self.stats.transfer_fallbacks += 1
            else:
                self.stats.swap_fallbacks += 1
            res.kind = "recompute"
            return False
        s = len(res.ctx)
        self.alloc.alloc(req.rid)
        try:
            try:
                self.alloc.reserve(req.rid, s)
            except PoolExhausted:
                if (self.prefix is None
                        or not self.prefix.evict_unused(self.alloc)):
                    raise
                self.alloc.reserve(req.rid, s)
        except PoolExhausted:
            self.alloc.release(req.rid)
            raise
        pids = self.alloc.tables[req.rid]
        assert len(pids) == entry.n_pages, "swap-in page count drift"
        m = next_pow2(max(1, len(pids)))
        idx = jnp.asarray(list(pids) + [0] * (m - len(pids)), jnp.int32)
        self.cache = self._scatter_pages(self.cache, self._dev(idx),
                                         entry.data)
        self.host_tier.pop(req.rid)
        self._resume.pop(req.rid)
        self.slots[slot] = req
        self._htable[slot, :] = 0
        self._htable[slot, :len(pids)] = pids
        self._table_dirty = True
        self.pos = self.pos.at[slot].set(s)
        self._hpos[slot] = s
        self._replay_key(slot, req)
        self.tokens = self.tokens.at[slot, 0].set(res.pending)
        if self.draft is not None:
            # the draft's dense cache was not swapped (it is derived state:
            # a prefill over the context rebuilds it, and coupled sampling
            # means draft differences can never change emitted tokens)
            self._draft_prefill_slot(slot, req, tokens=res.ctx)
        if req.rid in self._transfer_rids:
            self._transfer_rids.discard(req.rid)
            self.stats.prefill_imports += 1
            self.stats.transfer_bytes += entry.nbytes
        else:
            self.stats.swap_ins += 1
            self.stats.swap_bytes += entry.nbytes
        self._track_peaks()
        return True

    @staticmethod
    def _scatter_slot_cache(cache, cache1, slot: int):
        """Scatter a single-request prefill cache into the batch cache at
        ``slot``.  Stacked leaves (under blocks/dec) carry batch at axis 1;
        remainder leaves at axis 0.  Shorter prompt caches are padded
        (zeros for k/v — masked by kv_valid_len; -1e9 for kpos = empty)."""

        def place(path, tgt, upd):
            names = [str(getattr(p, "key", getattr(p, "name", ""))) for p in path]
            batch_ax = 1 if any(n in ("blocks", "dec") for n in names) else 0
            for ax in range(upd.ndim):
                if ax != batch_ax and upd.shape[ax] != tgt.shape[ax]:
                    pad = [(0, 0)] * upd.ndim
                    pad[ax] = (0, tgt.shape[ax] - upd.shape[ax])
                    cv = -10**9 if upd.dtype == jnp.int32 else 0
                    upd = jnp.pad(upd, pad, constant_values=cv)
            return jax.lax.dynamic_update_slice_in_dim(
                tgt, upd.astype(tgt.dtype), slot, batch_ax)

        return jax.tree_util.tree_map_with_path(place, cache, cache1)

    # ------------------------------------------------------------------
    # dense prefill (whole prompt, one dispatch)
    # ------------------------------------------------------------------
    def _prefill_into_slot(self, slot: int, req: Request):
        """Prefill a single request, then scatter its cache into the batch
        cache at ``slot``.  A preempted request resumes here by
        re-prefilling its recorded context (prompt + emitted tokens) and
        re-feeding — not re-sampling — its pending token."""
        res = self._resume.get(req.rid)
        prompt = req.prompt if res is None else res.ctx
        s = int(prompt.shape[0])
        if self.bucket_prompts:
            bucket = min(next_pow2(max(8, s)), self.max_len)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :s] = prompt
            if bucket not in self._seen_prefill_shapes:
                self._seen_prefill_shapes.add(bucket)
                self.stats.prefill_retraces += 1
            cache1, last_logits = self._prefill(
                self.params, jnp.asarray(padded), jnp.int32(s))
        else:
            if s not in self._seen_prefill_shapes:
                self._seen_prefill_shapes.add(s)
                self.stats.prefill_retraces += 1
            cache1, last_logits = self.bundle.prefill(
                self.params, dict(tokens=prompt[None, :]))

        self.cache = self._scatter_slot_cache(self.cache, cache1, slot)
        self.slots[slot] = req
        self.pos = self.pos.at[slot].set(s)
        self._hpos[slot] = s
        if res is None:
            self._assign_key(slot, req)
            tok0 = self._seed_token(slot, np.asarray(last_logits)[0])
            req.out_tokens.append(tok0)
            self.stats.prompt_tokens += s
            self.stats.tokens_out += 1
        else:
            self._resume.pop(req.rid)
            self._replay_key(slot, req)
            tok0 = int(res.pending)
            self.stats.recompute_resumes += 1
        self.tokens = self.tokens.at[slot, 0].set(tok0)
        self.stats.prefills += 1

    # ------------------------------------------------------------------
    # paged admission + chunked prefill
    # ------------------------------------------------------------------
    def _track_peaks(self) -> None:
        if self.alloc is not None:
            self.stats.pages_peak = max(self.stats.pages_peak,
                                        self.alloc.pages_in_use)
        if self.ralloc is not None:
            self.stats.ring_pages_peak = max(self.stats.ring_pages_peak,
                                             self.ralloc.pages_in_use)

    def _paged_admit_slot(self, slot: int, req: Request) -> None:
        """Attach the cached prompt prefix (shared read-only pages), then
        reserve pages for the whole prompt on every pool the stack uses
        (full table + windowed ring) — all-or-nothing, so admission either
        sticks or backs off cleanly (:class:`PoolExhausted`).

        Preempted requests re-enter here: swap-resumes stream their pages
        back (falling back to recompute if the host copy fails its
        checksum), recompute-resumes ride the normal chunked-prefill path
        over their recorded context — the original prompt pages typically
        hit the prefix cache, so only the generated tail recomputes."""
        res = self._resume.get(req.rid)
        if res is not None and res.kind == "swap" \
                and self._swap_in_slot(slot, req, res):
            return
        prompt = req.prompt if res is None else res.ctx
        s = int(prompt.shape[0])
        if s > self.max_len:
            raise ValueError(f"prompt ({s}) exceeds max_len ({self.max_len})")
        if self.alloc is not None:
            need = -(-s // self.page)
            if need > self.num_pages - 1:
                # no amount of backpressure can ever admit this one — waiting
                # would silently drop it (and head-of-line-block the queue)
                raise ValueError(
                    f"prompt needs {need} pages ({s} tokens) but the pool "
                    f"holds only {self.num_pages - 1}; raise num_pages")
        if self.ralloc is not None:
            need = min(-(-s // self.page), self.ralloc.ring_slots)
            if need > self.num_ring_pages - 1:
                raise ValueError(
                    f"prompt needs {need} ring pages but the ring pool "
                    f"holds only {self.num_ring_pages - 1}; raise "
                    "num_ring_pages")
        hit_len = 0
        hashes: List[str] = []
        if self.alloc is not None:
            self.alloc.alloc(req.rid)
            if self.prefix is not None:
                hashes = page_hashes(prompt, self.page)
                # cap at (s-1) tokens: the last token must be computed so
                # the final chunk yields the logits that seed decoding
                usable = (s - 1) // self.page
                pages = self.prefix.lookup(hashes[:usable],
                                           alloc=self.alloc)
                if pages:
                    hit_len = len(pages) * self.page
                    self.alloc.attach(req.rid, pages, hit_len)
        if self.ralloc is not None:
            self.ralloc.alloc(req.rid)
        try:
            if self.alloc is not None:
                try:
                    self.alloc.reserve(req.rid, s)
                except PoolExhausted:
                    if (self.prefix is None
                            or not self.prefix.evict_unused(self.alloc)):
                        raise
                    self.alloc.reserve(req.rid, s)
            if self.ralloc is not None:
                self.ralloc.reserve(req.rid, s)
        except PoolExhausted:
            if self.alloc is not None:
                self.alloc.release(req.rid)
            if self.ralloc is not None:
                self.ralloc.release(req.rid)
            raise
        self._hashes[req.rid] = hashes
        self.slots[slot] = req
        self._pending[slot] = hit_len
        self._hpos[slot] = 0  # no stale position while the prompt builds
        if res is None:  # a resume's context was already counted admitted
            self.stats.prompt_tokens += s
            self.stats.prefix_hit_tokens += hit_len
        self._track_peaks()
        # the batch table row stays null until prefill completes: masked
        # decode ticks must not write through a half-built row

    def _prefill_tick(self, slot: int) -> None:
        """Advance one pending slot by ONE chunk (<= prefill_chunk tokens).
        run_to_completion interleaves these with decode windows, so a long
        prompt admits without stalling in-flight decodes."""
        req = self.slots[slot]
        with TraceAnnotation("serve.prefill_chunk", rid=req.rid):
            res = self._resume.get(req.rid)
            prompt = req.prompt if res is None else res.ctx
            s = int(prompt.shape[0])
            off = self._pending[slot]
            c = min(self.prefill_chunk, s - off)
            cb = (min(next_pow2(max(8, c)), self.prefill_chunk)
                  if self.bucket_prompts else c)
            if ("chunk", cb) not in self._seen_prefill_shapes:
                self._seen_prefill_shapes.add(("chunk", cb))
                self.stats.prefill_retraces += 1
            chunk = np.zeros((1, cb), np.int32)
            chunk[0, :c] = prompt[off:off + c]
            row = self.alloc.tables[req.rid] if self.alloc is not None else []
            trow = np.zeros((1, max(1, self.pages_per_seq)), np.int32)
            trow[0, :len(row)] = row
            rrow = np.zeros((1, max(1, self.ring_slots)), np.int32)
            if self.ralloc is not None:
                rring = self.ralloc.tables[req.rid]
                rrow[0, :len(rring)] = rring
            self.cache, logits = self._paged_prefill(
                self.params, self.cache, jnp.asarray(chunk),
                jnp.asarray([off], jnp.int32),
                dict(full=jnp.asarray(trow), ring=jnp.asarray(rrow)),
                jnp.asarray([c], jnp.int32), jnp.int32(slot))
            self.stats.prefill_chunks += 1
            self._chunks_since_decode += 1
            off += c
            if off < s:
                self._pending[slot] = off
                return
            # prompt complete: seed decoding and publish the table rows
            del self._pending[slot]
            if self.prefix is not None:
                for i, h in enumerate(self._hashes.get(req.rid, [])):
                    if self.prefix.register(h, row[i]):
                        self.alloc.pin(row[i])
            self._hashes.pop(req.rid, None)
            self._htable[slot, :] = 0
            self._htable[slot, :len(row)] = row
            if self.ralloc is not None:
                rring = self.ralloc.tables[req.rid]
                self._hrtable[slot, :] = 0
                self._hrtable[slot, :len(rring)] = rring
            self._table_dirty = True
            self.pos = self.pos.at[slot].set(s)
            self._hpos[slot] = s
            if res is None:
                self._assign_key(slot, req)
                with TraceAnnotation("serve.device_wait"):
                    last = np.asarray(logits)[0]
                tok0 = self._seed_token(slot, last)
                req.out_tokens.append(tok0)
                self.stats.tokens_out += 1
            else:
                # recompute-resume: the context's last logits re-derive a
                # token that was already emitted — re-feed it, never
                # re-sample, and fast-forward the PRNG chain to where the
                # preempted run stood
                self._resume.pop(req.rid)
                self._replay_key(slot, req)
                tok0 = int(res.pending)
                self.stats.recompute_resumes += 1
            self.tokens = self.tokens.at[slot, 0].set(tok0)
            if self.draft is not None:
                self._draft_prefill_slot(
                    slot, req, tokens=None if res is None else res.ctx)
            self.stats.prefills += 1

    def _draft_prefill_slot(self, slot: int, req: Request,
                            tokens: Optional[np.ndarray] = None) -> None:
        """Build the draft model's dense cache for a freshly admitted slot
        (or, with ``tokens``, rebuild it over a resumed request's context —
        the draft cache is derived state, and coupled-sample verification
        means a rebuilt draft can only change throughput, never output).
        The draft is pure full attention (validated in ``_init_spec``), so
        the prompt buckets to a pow2 length and the padded tail is masked by
        ``valid_len`` — one trace per bucket, like the target's prefill."""
        toks = req.prompt if tokens is None else tokens
        s = int(toks.shape[0])
        bucket = min(next_pow2(max(8, s)), self.max_len)
        if ("draft", bucket) not in self._seen_prefill_shapes:
            self._seen_prefill_shapes.add(("draft", bucket))
            self.stats.prefill_retraces += 1
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :s] = toks
        dcache1, _ = self._draft_prefill(
            self.draft_params, jnp.asarray(padded), jnp.int32(s))
        self.draft_cache = self._scatter_slot_cache(
            self.draft_cache, dcache1, slot)

    def _admit(self) -> None:
        with TraceAnnotation("serve.admit"):
            while self.queue:
                if len(self.queue) > 1:
                    self.sched.order_queue(self.queue, self._arrival)
                req = self.queue[0]
                slot = self._free_slot()
                if slot is None:
                    # no slot: a strictly-lower-priority victim yields its
                    # seat (uniform priorities — the default — never preempt
                    # here)
                    victim = self._pick_victim(below=req.priority)
                    if victim is None:
                        break
                    self.preempt(victim)
                    continue
                if self.backend == "paged":
                    try:
                        self._paged_admit_slot(slot, req)
                    except PoolExhausted:
                        victim = self._pick_victim(below=req.priority)
                        if victim is None:
                            # backpressure: the request stays queued; pages
                            # free as in-flight requests finish
                            self.stats.pool_stalls += 1
                            break
                        self.preempt(victim)
                        continue
                    self.queue.pop(0)
                else:
                    self.queue.pop(0)
                    self._prefill_into_slot(slot, req)
            if self.backend == "paged":
                for slot in self.sched.prefill_order(
                        list(self._pending),
                        lambda i: self.slots[i].priority):
                    self._prefill_tick(slot)

    # ------------------------------------------------------------------
    def _budgets(self, n: int) -> np.ndarray:
        """Per-slot token budget for an n-tick window: remaining request
        quota, capped by the cache length guard.  Pending-prefill slots sit
        at zero until their prompt completes."""
        budgets = np.zeros((self.bsz,), np.int64)
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            if self.backend == "paged" and i in self._pending:
                continue
            remaining = req.max_new_tokens - len(req.out_tokens)
            cap = self.max_len - 1 - self._hpos[i]
            budgets[i] = max(0, min(remaining, cap, n))
        return budgets

    def _reserve_window_pages(self, budgets: np.ndarray) -> np.ndarray:
        """Pre-allocate pages covering each slot's window budget on every
        pool the stack uses (page allocation is host-side; the fused loop
        must never need a page).  Ring pools rotate in place past their
        window, so steady-state windowed decode allocates nothing.  Pool
        pressure shrinks budgets (possibly to zero — the slot waits) after
        evicting prefix-cache pages nothing references."""
        blocked = np.zeros((self.bsz,), bool)
        for i, req in enumerate(self.slots):
            if req is None or budgets[i] == 0:
                continue
            target = int(self._hpos[i] + budgets[i])
            feasible = target
            if self.alloc is not None:
                feasible = self.alloc.can_grow(req.rid, target)
                if feasible < target and self.prefix is not None:
                    self.prefix.evict_unused(self.alloc)
                    feasible = self.alloc.can_grow(req.rid, target)
            if self.ralloc is not None:
                feasible = min(feasible,
                               self.ralloc.can_grow(req.rid, target))
            grant = max(0, feasible - int(self._hpos[i]))
            if grant < budgets[i]:
                budgets[i] = grant
                blocked[i] = grant == 0
            if budgets[i] > 0:
                target = int(self._hpos[i] + budgets[i])
                if self.alloc is not None:
                    fresh = self.alloc.reserve(req.rid, target)
                    if fresh:
                        row = self.alloc.tables[req.rid]
                        self._htable[i, :len(row)] = row
                        self._table_dirty = True
                if self.ralloc is not None:
                    fresh = self.ralloc.reserve(req.rid, target)
                    if fresh:
                        rring = self.ralloc.tables[req.rid]
                        self._hrtable[i, :len(rring)] = rring
                        self._table_dirty = True
        self._track_peaks()
        return blocked

    def decode_many(self, n: int) -> int:
        """Run up to ``n`` decode ticks in ONE fused dispatch (sampling on
        device, per-slot budgets masked in-loop), then harvest the produced
        token block with a single device->host transfer.  With a draft
        model attached the dispatch is one speculative draft->verify round
        instead, emitting up to ``spec_k + 1`` tokens per slot.  Returns
        the number of real tokens produced."""
        with TraceAnnotation("serve.decode"):
            if self.draft is not None:
                n = min(n, self.spec_k + 1)
            with TraceAnnotation("serve.reserve"):
                budgets = self._window_budgets(n)
            if budgets is None:
                return 0
            self.stats.prefill_burst_max = max(self.stats.prefill_burst_max,
                                               self._chunks_since_decode)
            self._chunks_since_decode = 0
            if self.draft is not None:
                return self._spec_dispatch(budgets)
            n_run = min(n, next_pow2(int(budgets.max())))  # pow2: few traces
            with TraceAnnotation("serve.dispatch"):
                steps_h = np.minimum(budgets, n_run)
                steps = jnp.asarray(steps_h, jnp.int32)
                if self.backend == "paged":
                    self._count_kv_pages(steps_h, n_run)
                    (self.cache, self.tokens, self.pos, self.keys,
                     out) = self._paged_decode_many(
                        n_run, self.params, self.cache, self.tokens, self.pos,
                        steps, self.keys, self._table)
                else:
                    (self.cache, self.tokens, self.pos, self.keys,
                     out) = self._decode_many(
                        n_run, self.params, self.cache, self.tokens, self.pos,
                        steps, self.keys)
            self.stats.decode_steps += n_run
            self.stats.decode_dispatches += 1

            with TraceAnnotation("serve.device_wait"):
                out_np = np.asarray(out)  # (n_run, B) — the one host sync
            with TraceAnnotation("serve.unpack"):
                produced = 0
                for i, req in enumerate(self.slots):
                    if req is None or (self.backend == "paged"
                                       and i in self._pending):
                        continue
                    adv = int(min(budgets[i], n_run))
                    req.out_tokens.extend(int(t) for t in out_np[:adv, i])
                    self._hpos[i] += adv
                    produced += adv
                    if req.done or self._hpos[i] >= self.max_len - 1:
                        self._release_finished(i)
                self.stats.tokens_out += produced
            return produced

    def _count_kv_pages(self, steps: np.ndarray, n_run: int) -> None:
        """Add an ``n_run``-tick window's page reads to the stats: at tick
        t a slot with t < steps reads its first ceil((pos + t + 1) / page)
        table entries (every ring slot), an idle slot none."""
        tick = np.arange(n_run)[:, None]
        act = tick < steps[None, :]
        need = -(-(self._hpos[None, :] + tick + 1) // self.page)
        for width, ring in ((self.pages_per_seq, False),
                            (self.ring_slots, True)):
            if width == 0:
                continue
            live = width if ring else np.minimum(need, width)
            self.stats.kv_pages_live += int((act * live).sum())
            self.stats.kv_pages_table += n_run * self.bsz * width

    def _window_budgets(self, n: int) -> Optional[np.ndarray]:
        """Each slot's token budget for an ``n``-tick window, with the pages
        it needs reserved and the device page table in sync.  Slots that can
        never advance are retired first.  None when no slot can advance."""
        budgets = self._budgets(n)
        blocked = (self._reserve_window_pages(budgets)
                   if self.backend == "paged"
                   else np.zeros((self.bsz,), bool))
        retired = 0
        for i, req in enumerate(self.slots):
            if req is None or budgets[i] != 0 or blocked[i]:
                continue
            if self.backend == "paged" and i in self._pending:
                continue
            # done already (e.g. max_new_tokens=1 satisfied by prefill)
            # or pinned at the cache-length guard: retire the slot now,
            # otherwise it would never advance and never free
            self._release_finished(i)
            retired += 1
        if retired and self.backend == "paged" and blocked.any():
            # retired slots returned pages: pool-blocked slots retry
            budgets = self._budgets(n)
            blocked = self._reserve_window_pages(budgets)
        top = int(budgets.max(initial=0))
        if top == 0:
            if blocked.any() and not self._pending:
                # controlled shedding before the hard stop: preempt ONE
                # victim (any priority — everyone is blocked) so the
                # survivors inherit its pages; a lone blocked slot has
                # nobody to yield to, so the raise below still guards the
                # truly-undersized pool
                active = [i for i, r in enumerate(self.slots) if r is not None]
                victim = (self._pick_victim() if len(active) > 1 else None)
                if victim is not None:
                    self.preempt(victim)
                    budgets = self._budgets(n)
                    blocked = self._reserve_window_pages(budgets)
                    top = int(budgets.max(initial=0))
            if top == 0:
                if blocked.any() and not self._pending:
                    in_use = sum(a.pages_in_use
                                 for a in (self.alloc, self.ralloc)
                                 if a is not None)
                    free = sum(len(a.free)
                               for a in (self.alloc, self.ralloc)
                               if a is not None)
                    raise PoolExhausted(
                        "every active slot is pool-blocked and nothing can "
                        "free pages: the pool is smaller than the live "
                        "working set", pool="engine",
                        num_pages=(self.num_pages
                                   + (self.num_ring_pages if self.ralloc
                                      else 0)),
                        live_pages=in_use, free_pages=free)
                return None
        if self.backend == "paged" and self._table_dirty:
            self._sync_table()
        return budgets

    def _spec_dispatch(self, budgets: np.ndarray) -> int:
        """One speculative draft->verify round in a single fused dispatch.

        The draft proposes ``spec_k`` tokens, the target verifies them all
        (plus the pending token) in one batched multi-token
        ``paged_verify`` step, and each slot advances by its accepted
        prefix + 1 — coupled sampling (see :func:`_spec_decode_many_impl`)
        guarantees the emitted stream is exactly what vanilla decode would
        have produced.  Afterwards each slot's page reservation is rolled
        back to its accepted length: pages covering only rejected suffix
        rows return to the pool (shared prefix pages are refcounted, never
        mutated)."""
        with TraceAnnotation("serve.dispatch"):
            steps = jnp.asarray(budgets, jnp.int32)
            (self.cache, self.draft_cache, self.tokens, self.pos, self.keys,
             out, meta) = self._spec_decode(
                self.params, self.draft_params, self.cache, self.draft_cache,
                self.tokens, self.pos, steps, self.keys, self._table)
        # one spec round always advances every unblocked slot >= 1 token,
        # so a "tick" for progress accounting is one dispatch
        self.stats.decode_steps += 1
        self.stats.decode_dispatches += 1
        self.stats.spec_steps += 1

        with TraceAnnotation("serve.device_wait"):
            out_np = np.asarray(out)    # (B, k+1) — the one host sync
            meta_np = np.asarray(meta)  # (3, B): emitted / accepted / proposed
        with TraceAnnotation("serve.unpack"):
            produced = 0
            for i, req in enumerate(self.slots):
                if req is None or i in self._pending or budgets[i] == 0:
                    continue
                adv = int(meta_np[0, i])
                req.out_tokens.extend(int(t) for t in out_np[i, :adv])
                self._hpos[i] += adv
                produced += adv
                self.stats.draft_tokens += int(meta_np[2, i])
                self.stats.draft_accepted += int(meta_np[1, i])
                # rejected-suffix rollback: the window reservation ran ahead
                # to hpos + budget; shrink it to what was actually emitted
                self.alloc.truncate(req.rid, int(self._hpos[i]))
                if req.done or self._hpos[i] >= self.max_len - 1:
                    self._release_finished(i)
            self.stats.tokens_out += produced
        return produced

    def _release_finished(self, i: int) -> None:
        """Retire slot ``i``: paged pages go back to their pools
        *immediately* (prefix-pinned ones persist for future hits) and the
        slot's table rows revert to the null page so masked writes stay
        harmless."""
        req = self.slots[i]
        self.slots[i] = None
        if self.backend == "paged":
            if self.alloc is not None:
                self.alloc.release(req.rid)
            if self.ralloc is not None:
                self.ralloc.release(req.rid)
            self._hashes.pop(req.rid, None)
            self._htable[i, :] = 0
            self._hrtable[i, :] = 0
            self._table_dirty = True

    # ------------------------------------------------------------------
    def step(self) -> bool:
        """Admit queued requests, run one decode tick.  False when idle.
        (Compatibility wrapper: one-tick window of the fused path.)"""
        self._admit()
        if not any(s is not None for s in self.slots):
            return False
        self.decode_many(1)
        return True

    def run_to_completion(self, max_ticks: int = 10_000) -> ServeStats:
        """Serve until queue and slots drain; ``max_ticks`` bounds the device
        decode ticks executed (``ServeStats.decode_steps``)."""
        start = self.stats.decode_steps
        while self.stats.decode_steps - start < max_ticks:
            self._admit()
            if not any(s is not None for s in self.slots):
                break
            # every iteration makes progress: _admit advances each pending
            # prefill one chunk, decode_many produces tokens or retires
            # zero-budget slots (pool-blocked slots wait on those releases)
            self.decode_many(self.window)
        return self.stats


def _named(name: str, fn, *args, **kwargs):
    """``functools.partial(fn, *args, **kwargs)`` called ``name``: ``jax.jit``
    names the program's module ``jit_<name>``, which is how a profile finds
    it (an unnamed partial compiles to ``jit__unknown``)."""
    part = functools.partial(fn, *args, **kwargs)
    part.__name__ = name
    return part


def _gather_pages_impl(cache, pids):
    """Take ``pids`` along every pool leaf's page axis: the device half of
    a swap-out.  Under TP the pools are sharded on kv-heads, the gather
    axis is pages — each shard gathers its own head stripe."""

    def take(path, leaf):
        return jnp.take(leaf, pids, axis=page_axis(path, leaf))

    return jax.tree_util.tree_map_with_path(take, cache)


def _scatter_pages_impl(cache, pids, data):
    """Write gathered page data back at (new) page ids: the device half of
    a swap-in.  Padding lanes all target the reserved null page with the
    bytes it held at gather time — duplicate writes of one value, so the
    scatter stays deterministic and live pages are never touched."""

    def put(path, leaf, upd):
        ax = page_axis(path, leaf)
        upd = jnp.asarray(upd, leaf.dtype)
        if ax == 0:
            return leaf.at[pids].set(upd)
        return leaf.at[:, pids].set(upd)

    return jax.tree_util.tree_map_with_path(put, cache, data)


def _gather_logits(bundle: ModelBundle, logits):
    """TP: constrain the step's final logits replicated — ONE all-gather
    per step, placed so token selection (argmax or sample) runs on full
    replicated rows.  The per-slot PRNG chains therefore never see the
    mesh, which is what keeps a sharded drain bitwise identical to the
    single-device engine.  No-op off-mesh."""
    mesh = getattr(bundle.flags, "mesh", None)
    if mesh is None:
        return logits
    return jax.lax.with_sharding_constraint(
        logits, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()))


def _select_next(sampling: SamplingParams, logits, keys, act):
    """One in-loop token selection: greedy argmax (keys untouched — zero
    PRNG state consumed) or one split-and-draw per active slot.  Masked
    slots keep their key: a frozen slot replays identically no matter how
    many masked ticks pass over it."""
    if sampling.greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), keys
    nk, sub = split_keys(keys)
    nxt = sample_tokens(sub, logits, sampling)
    return nxt, jnp.where(act[:, None], nk, keys)


def _decode_many_impl(bundle: ModelBundle, sampling: SamplingParams, n: int,
                      params, cache, tokens, pos, steps, keys):
    """n fused decode ticks.  ``steps`` (B,) caps each slot: past its
    budget a slot is masked — tokens/pos/keys freeze, and its (discarded)
    cache writes re-store the same k/v at the frozen position, which is
    idempotent.  Returns (cache, tokens, pos, keys, out) with out (n, B)
    int32 (-1 = masked)."""
    bsz = tokens.shape[0]

    def body(i, carry):
        cache, tokens, pos, keys, out = carry
        logits, cache = bundle.decode_step(params, cache, tokens, pos)
        act = i < steps
        nxt, keys = _select_next(sampling, logits, keys, act)
        tokens = jnp.where(act[:, None], nxt[:, None], tokens)
        pos = jnp.where(act, pos + 1, pos)
        out = out.at[i].set(jnp.where(act, nxt, -1))
        return cache, tokens, pos, keys, out

    out0 = jnp.full((n, bsz), -1, jnp.int32)
    return jax.lax.fori_loop(0, n, body, (cache, tokens, pos, keys, out0))


def _paged_decode_many_impl(bundle: ModelBundle, plan, sampling: SamplingParams,
                            n: int, params, cache, tokens, pos, steps, keys,
                            table):
    """The paged twin of :func:`_decode_many_impl`: each tick writes k/v
    through the (loop-constant) page table and dispatches the
    ``paged_attention`` kernel under the engine's tuned ``plan`` (the
    kernel asserts the pool layout matches it).  Masked slots freeze
    exactly as in the dense path — their re-writes land on the same page
    slot (idempotent) or on the reserved null page (retired rows), never
    on live data."""
    bsz = tokens.shape[0]

    def body(i, carry):
        cache, tokens, pos, keys, out = carry
        act = i < steps
        logits, cache = bundle.paged_decode_step(params, cache, tokens, pos,
                                                 table, plan, act)
        nxt, keys = _select_next(sampling, _gather_logits(bundle, logits),
                                 keys, act)
        tokens = jnp.where(act[:, None], nxt[:, None], tokens)
        pos = jnp.where(act, pos + 1, pos)
        out = out.at[i].set(jnp.where(act, nxt, -1))
        return cache, tokens, pos, keys, out

    out0 = jnp.full((n, bsz), -1, jnp.int32)
    return jax.lax.fori_loop(0, n, body, (cache, tokens, pos, keys, out0))


def _spec_decode_many_impl(bundle: ModelBundle, draft: ModelBundle, plan,
                           sampling: SamplingParams, k: int, params, dparams,
                           cache, dcache, tokens, pos, steps, keys, table):
    """One speculative round, fully on device.

    The draft proposes ``k`` tokens autoregressively from its dense cache;
    the target verifies ``[pending, d_0 .. d_{k-1}]`` in ONE multi-token
    ``paged_verify`` dispatch (per-position logits).  Coupled sampling
    makes acceptance exact rather than approximate: both models draw from
    the SAME per-position subkey chain the vanilla loop would walk (one
    split per emitted token), the emitted token is always the *target's*
    draw, and a draft proposal is accepted iff it equals that draw.  The
    emitted stream — and the carried key after it — is therefore
    bit-identical to vanilla decoding by construction; the draft only
    controls how many tokens each dispatch advances.

    steps (B,) budgets each slot's emission this round (0 = frozen).
    Returns (cache, dcache, tokens, pos, keys, out, meta):
      out  (B, k+1) int32 — emitted tokens left-packed, -1 past the count
      meta (3, B)   int32 — [emitted m, accepted draft tokens, proposed]
    """
    bsz = tokens.shape[0]
    cv = jnp.clip(steps, 0, k + 1)                 # verify width per slot
    act = steps > 0

    if sampling.greedy:
        subs = jnp.zeros((bsz, k + 1, 2), jnp.uint32)
        carried = jnp.zeros((bsz, k + 2, 2), jnp.uint32)
    else:
        subs, carried = subkey_chain(keys, k + 1)

    # -- draft: k proposals + one extra step that only lands d_{k-1}'s KV
    # row (the bonus token's next-round attention needs it) ---------------
    def dbody(i, carry):
        dcache, dtok, drafts = carry
        dlogits, dcache = draft.decode_step(dparams, dcache, dtok, pos + i)
        dlogits = _gather_logits(draft, dlogits)
        if sampling.greedy:
            d = jnp.argmax(dlogits, axis=-1).astype(jnp.int32)
        else:
            d = sample_tokens(subs[:, i], dlogits, sampling)
        d = jnp.where(i < k, d, -1)
        drafts = jax.lax.dynamic_update_slice_in_dim(
            drafts, d[None], i, axis=0)
        return dcache, jnp.where(i < k, d, dtok[:, 0])[:, None], drafts

    drafts0 = jnp.full((k + 1, bsz), -1, jnp.int32)
    dcache, _, drafts = jax.lax.fori_loop(
        0, k + 1, dbody, (dcache, tokens, drafts0))
    drafts = drafts[:k].T                          # (B, k)

    # -- target: one batched verify over [pending, d_0 .. d_{k-1}] --------
    verify_tokens = jnp.concatenate([tokens, drafts], axis=1)  # (B, k+1)
    cache, logits = bundle.paged_verify(params, cache, verify_tokens, pos,
                                        table, cv, plan)       # (B, k+1, V)
    logits = _gather_logits(bundle, logits)
    if sampling.greedy:
        tsamp = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (B, k+1)
    else:
        tsamp = jax.vmap(
            lambda s, l: sample_tokens(s, l, sampling))(subs, logits)

    # -- acceptance: longest matching prefix, then the target's token -----
    match = drafts == tsamp[:, :k]                 # (B, k)
    j = jnp.where(jnp.all(match, axis=1), k,
                  jnp.argmin(match.astype(jnp.int32), axis=1))  # first miss
    m = jnp.where(act, jnp.minimum(j + 1, cv), 0)  # emitted this round
    emit = jnp.arange(k + 1, dtype=jnp.int32)[None, :] < m[:, None]
    out = jnp.where(emit, tsamp, -1)               # (B, k+1)

    last = jnp.take_along_axis(
        tsamp, jnp.maximum(m - 1, 0)[:, None], axis=1)         # (B, 1)
    tokens = jnp.where((m > 0)[:, None], last, tokens)
    pos = pos + m
    if not sampling.greedy:
        nk = jnp.take_along_axis(
            carried, jnp.broadcast_to(m[:, None, None], (bsz, 1, 2)),
            axis=1)[:, 0]
        keys = jnp.where(act[:, None], nk, keys)

    acc = jnp.minimum(m, j)                        # bonus token isn't a draft
    prop = jnp.where(act, k, 0)
    meta = jnp.stack([m, acc, prop]).astype(jnp.int32)
    return cache, dcache, tokens, pos, keys, out, meta
