"""Analytic memory-performance model (paper Eqs. 1-6, TPU-translated).

The paper models HBM behaviour under a high-level toolchain with five numbers:
transaction latency ``T_l`` (Eq. 1), loop iteration interval ``tau_II``
(Eqs. 2-4: serialized / pipelined / pipelined-with-NO-outstanding), achieved
bandwidth (Eq. 5) and theoretical bandwidth (Eq. 6).  We keep the same model
and re-ground the constants in TPU v5e hardware; predictions feed the
benchmarks (each bench reports measured + modeled columns) and the autotuner.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

from repro.core.patterns import Knobs, Pattern


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (tile/page/bucket rounding)."""
    p = 1
    while p < n:
        p *= 2
    return p


@dataclass(frozen=True)
class TPUSpec:
    """Hardware constants (v5e numbers from the assignment brief)."""

    name: str = "tpu-v5e"
    peak_flops_bf16: float = 197e12       # per chip
    hbm_bw: float = 819e9                 # bytes/s per chip
    ici_bw: float = 50e9                  # bytes/s per link (collective term)
    hbm_bytes: int = 16 * 2**30           # capacity per chip
    vmem_bytes: int = 128 * 2**20         # on-chip buffer budget (BRAM analogue)
    # what one Pallas kernel may hold in VMEM at once: the compiler's
    # default scoped limit, far below the physical buffer above
    scoped_vmem_bytes: int = 16 * 2**20
    clock_hz: float = 940e6
    # modeled DMA transaction latency (HBM row + controller + DMA setup).
    # The FPGA paper measures 58 cycles idle / ~107 loaded at 300MHz-class
    # clocks; TPU HBM2e+DMA engines land in the same few-hundred-ns regime.
    dma_latency_s: float = 700e-9

    @property
    def dma_latency_cycles(self) -> float:
        return self.dma_latency_s * self.clock_hz


V5E = TPUSpec()

# v5e 2D torus: 4 ICI links/chip; the roofline collective term uses 1 link
# (worst-case single-axis collective) per the assignment formula.
ICI_LINKS_PER_CHIP = 4


# ---------------------------------------------------------------------------
# Paper equations
# ---------------------------------------------------------------------------

def t_l(spec: TPUSpec = V5E) -> float:
    """Eq. 1 — absolute transaction latency (seconds)."""
    return spec.dma_latency_s


def tau_ii_serialized(t_op: float, spec: TPUSpec = V5E) -> float:
    """Eq. 2 — blocked loop: every access waits for the previous access AND
    the dependent op: tau = T_l + T_o."""
    return t_l(spec) + t_op


def tau_ii_pipelined(spec: TPUSpec = V5E) -> float:
    """Eq. 3 — pipelined but dependence on returned data: tau = T_l."""
    return t_l(spec)


def tau_ii_outstanding(outstanding: int, spec: TPUSpec = V5E) -> float:
    """Eq. 4 (corrected steady-state form) — NO requests in flight:
    tau = max(1 cycle, T_l / NO)."""
    return max(1.0 / spec.clock_hz, t_l(spec) / max(1, outstanding))


def achieved_bw(total_bytes: float, wall_s: float) -> float:
    """Eq. 5 — achieved bandwidth from bytes moved and host-timed seconds."""
    return total_bytes / wall_s


def theoretical_bw(spec: TPUSpec = V5E) -> float:
    """Eq. 6 analogue — peak per-chip HBM bandwidth (the N*W*F/8e9 of a TPU
    is its published HBM number; DMA engines, not AXI channels, set N*W)."""
    return spec.hbm_bw


# ---------------------------------------------------------------------------
# Pattern throughput predictions (drives benchmarks + autotuner)
# ---------------------------------------------------------------------------

def predict_bw(pattern: Pattern, knobs: Knobs, spec: TPUSpec = V5E) -> float:
    """Predicted bytes/s for an engine running ``pattern`` with ``knobs``.

    Steady state per tile/touch: t = max(transfer_time, T_l / NO); the chase
    pattern forbids overlap entirely (NO == 1 by construction).
    """
    lat = t_l(spec)
    if pattern in (Pattern.SEQUENTIAL, Pattern.RS_TRA, Pattern.NEST):
        b = knobs.burst_bytes
        t = max(b / spec.hbm_bw, lat / max(1, knobs.outstanding))
        return min(spec.hbm_bw, b / t)
    if pattern == Pattern.STRIDED:
        # each touch moves unit_bytes of useful data but occupies the channel
        # for min(stride, page/unit) * unit worth of row activation; model as
        # useful fraction 1/stride down to the latency floor.
        b = knobs.unit_bytes
        t = max(b * knobs.stride / spec.hbm_bw, lat / max(1, knobs.outstanding))
        return min(spec.hbm_bw / max(1, knobs.stride), b / t)
    if pattern in (Pattern.RANDOM, Pattern.R_ACC, Pattern.RR_TRA):
        b = knobs.unit_bytes
        t = max(b / spec.hbm_bw, lat / max(1, knobs.outstanding))
        return min(spec.hbm_bw, b / t)
    if pattern == Pattern.CHASE:
        return knobs.unit_bytes / lat
    raise ValueError(pattern)


def aggregate_bw(pattern: Pattern, knobs: Knobs, spec: TPUSpec = V5E) -> float:
    """Multi-engine aggregate bytes/s (paper Tables 3-5 scaling).

    The paper scales bandwidth by instantiating parallel access engines over
    banked HBM; the TPU analogue is mesh shards, each streaming from its own
    HBM stack, so the aggregate is linear in the engine count.  The engine
    count should come from the active sharding policy's mesh shape
    (``repro.dist.sharding.ShardingPolicy.engines``), not be hardcoded —
    ``Knobs(engines=policy.engines(mesh))``.
    """
    return predict_bw(pattern, knobs, spec) * max(1, knobs.engines)


def min_outstanding_for_peak(burst_bytes: int, spec: TPUSpec = V5E) -> int:
    """Knee of the paper's Fig. 5: NO* = ceil(T_l * BW / burst)."""
    import math
    return max(1, math.ceil(t_l(spec) * spec.hbm_bw / max(1, burst_bytes)))


def vmem_ok(knobs: Knobs, spec: TPUSpec = V5E, budget_fraction: float = 0.5) -> bool:
    """The paper's BRAM constraint (Tables 3-5): buffering must fit VMEM."""
    return knobs.vmem_bytes() <= spec.vmem_bytes * budget_fraction


# ---------------------------------------------------------------------------
# Roofline terms (assignment formulas)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class RooflineTerms:
    compute_s: float
    memory_s: float
    collective_s: float
    hlo_flops: float
    hlo_bytes: float
    collective_bytes: float
    chips: int
    model_flops: float = 0.0

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def bound_s_no_overlap(self) -> float:
        """Conservative serial model: terms sum (no DMA/ICI/MXU overlap)."""
        return self.compute_s + self.memory_s + self.collective_s

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs — remat/redundancy waste detector."""
        return self.model_flops / self.hlo_flops if self.hlo_flops else 0.0

    @property
    def roofline_fraction(self) -> float:
        """How close the dominant-term time is to the pure-compute ideal for
        the *useful* (MODEL_FLOPS) work: ideal_s / bound (terms overlapped —
        the usual TPU model where DMA, ICI and MXU pipelines run
        concurrently)."""
        if not self.model_flops or not self.bound_s:
            return 0.0
        ideal = self.compute_s * self.useful_flops_ratio  # useful-compute time
        return ideal / self.bound_s

    @property
    def roofline_fraction_no_overlap(self) -> float:
        """Conservative variant: terms serialized (sum)."""
        if not self.model_flops or not self.bound_s_no_overlap:
            return 0.0
        ideal = self.compute_s * self.useful_flops_ratio
        return ideal / self.bound_s_no_overlap


def roofline(hlo_flops: float, hlo_bytes: float, collective_bytes: float,
             chips: int, model_flops: float = 0.0,
             spec: TPUSpec = V5E, per_chip: bool = True) -> RooflineTerms:
    """Assignment formulas.  ``per_chip=True`` means the inputs are already
    per-chip quantities (XLA:CPU cost_analysis reports per-device)."""
    scale = 1.0 if per_chip else 1.0 / chips
    return RooflineTerms(
        compute_s=hlo_flops * scale / spec.peak_flops_bf16,
        memory_s=hlo_bytes * scale / spec.hbm_bw,
        collective_s=collective_bytes * scale / spec.ici_bw,
        hlo_flops=hlo_flops * scale,
        hlo_bytes=hlo_bytes * scale,
        collective_bytes=collective_bytes * scale,
        chips=chips,
        model_flops=model_flops * scale,
    )
