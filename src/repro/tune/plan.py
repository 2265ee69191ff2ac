"""KernelPlan: the applied output of the autotuner (paper §5, closed-loop).

PR 2 built the measurement machinery (sweeps, calibration); this module is
the missing half of the loop: it turns ``tune_attention_blocks`` /
``tune_pattern`` output into a concrete, serializable *plan* — block sizes,
pipeline depth, dtype, interpret flag — that the Pallas kernels and their
model call sites consume as their default.  A plan is derived once per
``(kernel, shape signature, dtype, TPUSpec fingerprint)`` and cached
(:mod:`repro.tune.cache`); when a :class:`~repro.bench.calibrate.
CalibrationResult` is supplied the derivation runs against the *fitted*
spec, so measured mode changes the plans (and the fingerprint, so stale
analytic plans are never reused).
"""
from __future__ import annotations

import hashlib
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

from repro.core.autotune import tune_attention_blocks, tune_pattern
from repro.core.memmodel import (TPUSpec, V5E, next_pow2, predict_bw,
                                 vmem_ok)
from repro.core.patterns import Knobs, Pattern

# the kernels a plan can target (ops.py wrappers consume these; for the
# paged kernels the plan's bkv IS the page size — the pool is laid out from
# the plan, so tuning reshapes serving memory itself; paged_verify is the
# k-token speculative verify step over the same pool)
KERNELS = ("flash_attention", "decode_attention", "matmul", "paged_attention",
           "paged_verify")


def auto_interpret() -> bool:
    """The single backend heuristic every consumer shares: compile the
    Pallas kernel on a real TPU backend, run interpret mode elsewhere."""
    import jax
    return jax.default_backend() != "tpu"


def spec_fingerprint(spec: TPUSpec) -> str:
    """Short stable id of the constants that shape a tuning decision.

    Calibration replaces the spec (name + fitted constants), so a calibrated
    run fingerprints differently from the analytic one — that is the cache
    invalidation rule: new constants => new key => plans re-derived.
    """
    raw = (f"{spec.name}|{spec.hbm_bw:.6g}|{spec.dma_latency_s:.6g}"
           f"|{spec.vmem_bytes}|{spec.scoped_vmem_bytes}|{spec.clock_hz:.6g}")
    return hashlib.sha1(raw.encode()).hexdigest()[:12]


@dataclass(frozen=True)
class KernelPlan:
    """One tuned kernel configuration, ready to execute.

    Paper §5 knob -> plan field:
      burst size       -> ``bkv`` (the contiguous kv/rhs tile per DMA)
      outstanding (NO) -> ``pipeline_depth`` (multiple-buffering depth)
      unit width       -> ``dtype`` x lane tile (``unit_bytes`` property)
    ``interpret=None`` means auto: compile the Pallas kernel on a real TPU
    backend, run interpret mode elsewhere (CPU CI).
    """

    kernel: str
    bq: int
    bkv: int
    pipeline_depth: int = 2
    dtype: str = "bfloat16"
    interpret: Optional[bool] = None
    head_dim: int = 128
    predicted_gbps: float = 0.0
    source: str = "analytic"            # analytic | calibrated

    # ------------------------------------------------------------------
    @property
    def dtype_bytes(self) -> int:
        import jax.numpy as jnp
        return jnp.dtype(self.dtype).itemsize

    @property
    def unit_bytes(self) -> int:
        """Transaction width: one head row of the plan's dtype."""
        return max(1, self.head_dim * self.dtype_bytes)

    @property
    def burst_bytes(self) -> int:
        """Contiguous DMA size: the kv/rhs tile."""
        return max(1, self.bkv * self.head_dim * self.dtype_bytes)

    @property
    def page_size(self) -> int:
        """Paged-attention reading of ``bkv``: tokens per KV page.  The
        serving engine shapes its page pool from this, so the r_acc
        transaction-optimum rule reaches HBM layout, not just the kernel."""
        return self.bkv

    def knobs(self) -> Knobs:
        """The plan expressed in the paper's knob vocabulary (for vmem_ok /
        predict_bw round-trips)."""
        return Knobs(unit_bytes=self.unit_bytes, burst_bytes=self.burst_bytes,
                     outstanding=self.pipeline_depth)

    def vmem_bytes(self) -> int:
        """Resident buffering: q tile + f32 scratch rows + double-buffered
        kv tiles (mirrors ``tune_attention_blocks``'s budget formula)."""
        db = self.dtype_bytes
        return (self.bq * (self.head_dim + 4) * 4
                + self.pipeline_depth * self.bkv * self.head_dim * db * 2)

    def resolve_interpret(self) -> bool:
        if self.interpret is not None:
            return self.interpret
        return auto_interpret()

    # ------------------------------------------------------------------
    def to_dict(self) -> Dict[str, Any]:
        return {
            "kernel": self.kernel, "bq": self.bq, "bkv": self.bkv,
            "pipeline_depth": self.pipeline_depth, "dtype": self.dtype,
            "interpret": self.interpret, "head_dim": self.head_dim,
            "predicted_gbps": self.predicted_gbps, "source": self.source,
        }

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "KernelPlan":
        return cls(kernel=d["kernel"], bq=int(d["bq"]), bkv=int(d["bkv"]),
                   pipeline_depth=int(d.get("pipeline_depth", 2)),
                   dtype=d.get("dtype", "bfloat16"),
                   interpret=d.get("interpret"),
                   head_dim=int(d.get("head_dim", 128)),
                   predicted_gbps=float(d.get("predicted_gbps", 0.0)),
                   source=d.get("source", "analytic"))


# ---------------------------------------------------------------------------
# Derivation (the tune -> plan step)
# ---------------------------------------------------------------------------

def plan_key(kernel: str, shape_sig: Tuple[int, ...], dtype: str,
             spec: TPUSpec) -> str:
    sig = "x".join(str(int(s)) for s in shape_sig)
    return f"{kernel}|{sig}|{dtype}|{spec_fingerprint(spec)}"


def _resolve_spec(spec: Optional[TPUSpec], calibration) -> Tuple[TPUSpec, str]:
    if calibration is not None:
        return calibration.spec, "calibrated"
    return (spec or V5E), "analytic"


def _shrink_to_budget(bq: int, bkv: int, head_dim: int, db: int,
                      budget: float, depth: int) -> Tuple[int, int]:
    """Halve the kv (then q) tile until the scratch+buffer estimate fits —
    the tuner's feasibility guarantee must survive seq-length clamping and
    odd head dims the candidate grid never saw."""
    def vmem(bq_, bkv_):
        return bq_ * (head_dim + 4) * 4 + depth * bkv_ * head_dim * db * 2
    while vmem(bq, bkv) > budget and bkv > 8:
        bkv //= 2
    while vmem(bq, bkv) > budget and bq > 8:
        bq //= 2
    return max(8, bq), max(8, bkv)


def _flash_scoped_vmem(bq: int, bkv: int, head_dim: int, db: int) -> int:
    """Scoped VMEM the flash kernel holds: double-buffered q/k/v/out
    blocks, the f32 scratch (the (bq, 1) m/l rows pad to 128 lanes), f32
    copies of the tiles, and the (bq, bkv) f32 scores and probabilities.
    Checked against the TPU compiler: (1024, 1024) at head_dim 128 fits
    the 16 MiB v5e limit, (2048, 2048) does not."""
    blocks = 2 * (2 * bq + 2 * bkv) * head_dim * db
    scratch = bq * (2 * 128 + head_dim) * 4
    return blocks + scratch + (bq + 2 * bkv) * head_dim * 4 + 2 * bq * bkv * 4


def _matmul_scoped_vmem(tile: int, db: int) -> int:
    """Scoped VMEM of the tiled matmul at a square tile: double-buffered
    lhs/rhs/out tiles, the f32 accumulator and the f32 dot result."""
    return tile * tile * (6 * db + 8)


def derive_attention_plan(*, sq: int, skv: int, head_dim: int,
                          dtype: str = "bfloat16",
                          kernel: str = "flash_attention",
                          spec: Optional[TPUSpec] = None, calibration=None,
                          vmem_budget_fraction: float = 0.4) -> KernelPlan:
    """(bq, bkv) for the nest tiling from ``tune_attention_blocks`` under the
    (possibly calibrated) spec, clamped to the actual sequence lengths."""
    import jax.numpy as jnp
    spec, source = _resolve_spec(spec, calibration)
    db = jnp.dtype(dtype).itemsize
    bq, bkv = tune_attention_blocks(head_dim, dtype_bytes=db, spec=spec,
                                    vmem_budget_fraction=vmem_budget_fraction)
    bq, bkv = min(bq, max(8, sq)), min(bkv, max(8, skv))
    # the tuner budgets the physical buffer; the kernel gets the scoped
    # limit, so halve the larger tile until its real footprint fits that
    while (_flash_scoped_vmem(bq, bkv, head_dim, db) > spec.scoped_vmem_bytes
           and max(bq, bkv) > 8):
        if bkv >= bq:
            bkv //= 2
        else:
            bq //= 2
    knobs = Knobs(unit_bytes=head_dim * db, burst_bytes=bkv * head_dim * db,
                  outstanding=2)
    return KernelPlan(
        kernel=kernel, bq=bq, bkv=bkv, pipeline_depth=2, dtype=dtype,
        interpret=None, head_dim=head_dim,
        predicted_gbps=predict_bw(Pattern.NEST, knobs, spec) / 1e9,
        source=source)


def derive_decode_plan(*, seq_len: int, head_dim: int, dtype: str = "bfloat16",
                       spec: Optional[TPUSpec] = None, calibration=None,
                       vmem_budget_fraction: float = 0.4) -> KernelPlan:
    """Split-KV block for flash-decode: decode streams the whole cache once
    per token (the paper's `rs_tra` pure-bandwidth regime), so the kv block
    is the tuned sequential burst divided by the row width."""
    import jax.numpy as jnp
    spec, source = _resolve_spec(spec, calibration)
    db = jnp.dtype(dtype).itemsize
    tuned = tune_pattern(Pattern.RS_TRA, spec=spec,
                         vmem_budget_fraction=vmem_budget_fraction,
                         calibration=calibration)
    bkv = max(8, tuned.knobs.burst_bytes // max(1, head_dim * db))
    bkv = min(bkv, max(8, seq_len))
    _, bkv = _shrink_to_budget(8, bkv, head_dim, db,
                               spec.vmem_bytes * vmem_budget_fraction,
                               tuned.knobs.outstanding)
    return KernelPlan(
        kernel="decode_attention", bq=1, bkv=bkv,
        pipeline_depth=tuned.knobs.outstanding, dtype=dtype, interpret=None,
        head_dim=head_dim, predicted_gbps=tuned.predicted_gbps, source=source)


def derive_paged_plan(*, max_len: int, head_dim: int, dtype: str = "bfloat16",
                      spec: Optional[TPUSpec] = None, calibration=None,
                      vmem_budget_fraction: float = 0.4) -> KernelPlan:
    """Page size (``bkv``) for the paged-KV pool + kernel.

    Paged decode is the paper's `r_acc` engine: each sequence gathers its
    pages through a table indirection, so the *page* is the transaction.
    The advisor's rule is ``unit_bytes >= 512B``; bigger pages only add
    internal fragmentation (the resource axis of the paper's
    throughput-vs-resources tradeoff), so the page is the *smallest* pow2
    token count whose row block crosses that optimum — clamped to the
    sequence budget so a short ``max_len`` is never a single page.
    ``dtype`` is the dtype the pool *stores*: int8 KV pages halve the row
    width, so the derived page holds proportionally more tokens — the
    paper's data-width lever applied to HBM layout.  Pipeline depth
    (outstanding gathers) comes from the tuned r_acc knobs.
    """
    import jax.numpy as jnp
    spec, source = _resolve_spec(spec, calibration)
    db = jnp.dtype(dtype).itemsize
    row = max(1, head_dim * db)
    tuned = tune_pattern(Pattern.R_ACC, spec=spec,
                         vmem_budget_fraction=vmem_budget_fraction,
                         calibration=calibration)
    page = next_pow2(-(-512 // row))
    page = max(8, min(page, max(8, next_pow2(max_len) // 2)))
    return KernelPlan(
        kernel="paged_attention", bq=1, bkv=page,
        pipeline_depth=tuned.knobs.outstanding, dtype=dtype, interpret=None,
        head_dim=head_dim, predicted_gbps=tuned.predicted_gbps, source=source)


def derive_verify_plan(*, verify_tokens: int, max_len: int, head_dim: int,
                       dtype: str = "bfloat16",
                       spec: Optional[TPUSpec] = None, calibration=None,
                       vmem_budget_fraction: float = 0.4) -> KernelPlan:
    """Plan for the speculative k-token verify step.

    Verification reads the page pool exactly like paged decode (`r_acc`
    through the table), so the transaction unit — ``bkv``, the page —
    must match the pool the engine laid out from
    :func:`derive_paged_plan`.  The lever verification adds is *burst
    length*: ``bq`` becomes the verify width (pending token + k drafts),
    so one table walk serves ``verify_tokens`` query positions instead
    of one — the paper's tokens-per-transaction amortization.  The
    predicted bandwidth is the r_acc gather rate scaled by the reuse
    factor (each fetched page row now feeds up to ``verify_tokens``
    queries)."""
    base = derive_paged_plan(max_len=max_len, head_dim=head_dim, dtype=dtype,
                             spec=spec, calibration=calibration,
                             vmem_budget_fraction=vmem_budget_fraction)
    vt = max(1, int(verify_tokens))
    return KernelPlan(
        kernel="paged_verify", bq=vt, bkv=base.bkv,
        pipeline_depth=base.pipeline_depth, dtype=dtype, interpret=None,
        head_dim=head_dim, predicted_gbps=base.predicted_gbps * vt,
        source=base.source)


def derive_matmul_plan(*, m: int, n: int, k: int, dtype: str = "bfloat16",
                       spec: Optional[TPUSpec] = None,
                       calibration=None) -> KernelPlan:
    """Square tile for the tiled matmul: the largest MXU-aligned tile whose
    whole footprint fits the kernel's scoped VMEM."""
    import jax.numpy as jnp
    spec, source = _resolve_spec(spec, calibration)
    db = jnp.dtype(dtype).itemsize
    tile = 128
    for t in (128, 256, 512, 1024):
        if _matmul_scoped_vmem(t, db) <= spec.scoped_vmem_bytes:
            tile = t
    tile = min(tile, max(8, m), max(8, n), max(8, k))
    knobs = Knobs(unit_bytes=tile * db, burst_bytes=tile * tile * db,
                  outstanding=2)
    return KernelPlan(
        kernel="matmul", bq=tile, bkv=tile, pipeline_depth=2, dtype=dtype,
        interpret=None, head_dim=tile,
        predicted_gbps=predict_bw(Pattern.SEQUENTIAL, knobs, spec) / 1e9,
        source=source)


def derive_plan(kernel: str, *, shape_sig: Tuple[int, ...], dtype: str,
                spec: Optional[TPUSpec] = None, calibration=None) -> KernelPlan:
    """Dispatch on kernel name; ``shape_sig`` is the kernel's tuning-relevant
    shape tuple (see :func:`repro.tune.cache.plan_for` for the per-kernel
    signatures)."""
    if kernel == "flash_attention":
        sq, skv, head_dim = shape_sig
        return derive_attention_plan(sq=sq, skv=skv, head_dim=head_dim,
                                     dtype=dtype, spec=spec,
                                     calibration=calibration)
    if kernel == "decode_attention":
        seq_len, head_dim = shape_sig
        return derive_decode_plan(seq_len=seq_len, head_dim=head_dim,
                                  dtype=dtype, spec=spec,
                                  calibration=calibration)
    if kernel == "paged_attention":
        # optional trailing element: per-shard kv-head count under serve-side
        # TP — it never changes the page geometry (the 512B rule is per head
        # row) but keys the cache, so a calibration made on an N-way engine
        # re-derives independently of the single-device plan
        max_len, head_dim = shape_sig[:2]
        return derive_paged_plan(max_len=max_len, head_dim=head_dim,
                                 dtype=dtype, spec=spec,
                                 calibration=calibration)
    if kernel == "paged_verify":
        verify_tokens, max_len, head_dim = shape_sig[:3]
        return derive_verify_plan(verify_tokens=verify_tokens,
                                  max_len=max_len, head_dim=head_dim,
                                  dtype=dtype, spec=spec,
                                  calibration=calibration)
    if kernel == "matmul":
        m, n, k = shape_sig
        return derive_matmul_plan(m=m, n=n, k=k, dtype=dtype, spec=spec,
                                  calibration=calibration)
    raise ValueError(f"unknown kernel {kernel!r}; known: {KERNELS}")
