"""Roofline-term extraction from compiled XLA artifacts.

Sources (assignment formulas):
- ``compiled.cost_analysis()`` -> HLO_FLOPs, HLO_bytes (per-device on XLA:CPU)
- ``compiled.as_text()``       -> collective_bytes: sum of operand sizes over
  all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute.

Caveat handled here: XLA cost analysis counts a ``while`` (lax.scan) body
ONCE.  Full-depth dry-run compiles use scan (that is the deployable artifact
and the memory_analysis source), so for *cost* we compile the same cell in
roofline mode (layers unrolled at nb in {1,2}, inner scans replaced by
DAG-structured equivalents) and extrapolate affinely: cost(nb) = a + b*nb is
exact for repeated blocks (layer compute, per-layer collectives, and the
optimizer update are all affine in block count).
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

from repro.core.memmodel import RooflineTerms, TPUSpec, V5E, roofline

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# one HLO instruction line: "  %name = <ret-type> opcode(<operands>) ..."
_LINE_RE = re.compile(
    r"=\s*(?P<ret>\([^)]*\)|\S+)\s+"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?P<suffix>-start|-done)?\(")

_SHAPE_RE = re.compile(r"(?P<dt>[a-z0-9]+)\[(?P<dims>[0-9,]*)\]")


def _shape_bytes(token: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(token):
        dt = m.group("dt")
        if dt not in _DTYPE_BYTES:
            continue
        dims = m.group("dims")
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


_GROUPS_RE = re.compile(
    r"replica_groups=(?:\{\{(?P<first>[0-9, ]+)\}|\[(?P<gc>\d+),(?P<gs>\d+)\])")


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if not m:
        return 2
    if m.group("gs"):
        return int(m.group("gs"))
    return len(m.group("first").split(","))


def collective_stats(hlo_text: str) -> Tuple[float, Dict[str, Dict[str, float]]]:
    """Per-device wire bytes for every collective op.

    Operands are not inline-typed in optimized HLO, so bytes derive from the
    RESULT type + the replica-group size G (ring model):
      all-gather         result*(G-1)/G      (receives all other shards)
      reduce-scatter     result*(G-1)        (operand = result*G)
      all-reduce         2*result*(G-1)/G    (RS + AG phases)
      all-to-all         result*(G-1)/G
      collective-permute result
    ``-done`` halves of async pairs are skipped.
    """
    per: Dict[str, Dict[str, float]] = {}
    total = 0.0
    for line in hlo_text.splitlines():
        m = _LINE_RE.search(line)
        if not m or m.group("suffix") == "-done":
            continue
        op = m.group("op")
        rbytes = _shape_bytes(m.group("ret"))
        g = _group_size(line)
        if op == "all-gather":
            wire = rbytes * (g - 1) / g
        elif op == "reduce-scatter":
            wire = rbytes * (g - 1)
        elif op == "all-reduce":
            wire = 2 * rbytes * (g - 1) / g
        elif op == "all-to-all":
            wire = rbytes * (g - 1) / g
        else:  # collective-permute
            wire = rbytes
        d = per.setdefault(op, dict(count=0, bytes=0.0))
        d["count"] += 1
        d["bytes"] += wire
        total += wire
    return total, per


# ---------------------------------------------------------------------------
# Fusion-aware HBM byte estimate
# ---------------------------------------------------------------------------

# ops whose operands+outputs are genuine HBM traffic on TPU
_COUNTED_OPS = {
    "dot", "convolution", "fusion", "custom-call",
    "gather", "scatter", "dynamic-slice", "dynamic-update-slice",
    "copy", "transpose", "concatenate", "pad", "slice", "reverse",
    "reduce", "reduce-window", "sort", "select-and-scatter", "cholesky",
    "triangular-solve", "rng", "rng-bit-generator",
}
# pointwise/free ops assumed fused into neighbours (TPU fusion model)
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%(?P<name>[\w.\-]+)\s*=\s*"
    r"(?P<ret>\([^)]*\)|\S+?)\s+(?P<op>[\w\-]+)\((?P<args>[^)]*)\)")
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?(?P<name>[\w.\-]+)\s*\(")


def fused_bytes(hlo_text: str) -> float:
    return fused_bytes_detail(hlo_text)[0]


_META_SCOPE_RE = re.compile(r'op_name="([^"]*)"')


def fused_bytes_detail(hlo_text: str, scopes: Tuple[str, ...] = ("flash_inner",)
                       ) -> Tuple[float, Dict[str, float]]:
    """TPU-fusion-model HBM bytes: sum operand+output bytes over data-moving
    ops (dots, fusions, gathers, copies, reduces...), skipping pointwise ops
    (they fuse) and fusion/reducer *bodies* (their traffic is the call's).
    ``while`` bodies count once — same convention as cost_analysis FLOPs.

    Returns (total, {scope: bytes}) where bytes whose op_name metadata
    contains a scope keyword are attributed to it — used to quantify how much
    of the traffic a Pallas kernel would keep VMEM-resident."""
    # split into computations (header: "... (params) -> ret {")
    comps: Dict[str, list] = {}
    cur = None
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if stripped.endswith("{") and "->" in stripped:
            mc = _COMP_HDR_RE.match(stripped)
            if mc:
                cur = mc.group("name")
                comps[cur] = []
                continue
        if cur is None:
            continue
        if stripped == "}":
            cur = None
            continue
        comps[cur].append(line)
    # fusion + reducer bodies are internal; while bodies stay top-level
    internal: set = set()
    for lines in comps.values():
        for line in lines:
            for m in re.finditer(r"(?:calls|to_apply)=%?([\w.\-]+)", line):
                internal.add(m.group(1))

    total = 0.0
    by_scope: Dict[str, float] = {s: 0.0 for s in scopes}
    for name, lines in comps.items():
        if name in internal:
            continue
        sizes: Dict[str, int] = {}
        for line in lines:
            m = _INSTR_RE.match(line)
            if not m:
                continue
            nm, ret, op, args = m.group("name", "ret", "op", "args")
            rbytes = _shape_bytes(ret)
            sizes[nm] = rbytes
            if op in _COUNTED_OPS:
                ob = 0
                for a in args.split(","):
                    a = a.strip().lstrip("%")
                    ob += sizes.get(a, 0)
                total += rbytes + ob
                sm = _META_SCOPE_RE.search(line)
                if sm:
                    for s in scopes:
                        if s in sm.group(1):
                            by_scope[s] += rbytes + ob
                            break
    return total, by_scope


@dataclass(frozen=True)
class CellCost:
    flops: float
    bytes_raw: float      # cost_analysis "bytes accessed" (no-fusion bound)
    bytes_fused: float    # TPU-fusion-model estimate (memory-term source)
    collective: float
    bytes_flash_inner: float = 0.0  # subset of bytes_fused a Pallas flash
    #                                 kernel keeps VMEM-resident

    def __add__(self, other):
        return CellCost(self.flops + other.flops,
                        self.bytes_raw + other.bytes_raw,
                        self.bytes_fused + other.bytes_fused,
                        self.collective + other.collective,
                        self.bytes_flash_inner + other.bytes_flash_inner)

    def scale(self, k: float) -> "CellCost":
        return CellCost(self.flops * k, self.bytes_raw * k,
                        self.bytes_fused * k, self.collective * k,
                        self.bytes_flash_inner * k)


def cost_of(compiled) -> CellCost:
    ca = compiled.cost_analysis() or {}
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    txt = compiled.as_text()
    coll, _ = collective_stats(txt)
    fb, scopes = fused_bytes_detail(txt)
    return CellCost(flops, byts, fb, coll,
                    bytes_flash_inner=scopes.get("flash_inner", 0.0))


def affine_extrapolate(c_a: CellCost, c_b: CellCost, nb_a: int, nb_b: int,
                       nb_target: int) -> CellCost:
    """cost(nb) = base + slope*nb, from two measured points."""
    dn = nb_b - nb_a
    slope = (c_b + c_a.scale(-1)).scale(1.0 / dn)
    base = c_a + slope.scale(-nb_a)
    return base + slope.scale(nb_target)


def terms_from_cost(cost: CellCost, chips: int, model_flops_per_chip: float,
                    spec: TPUSpec = V5E) -> RooflineTerms:
    return roofline(cost.flops, cost.bytes_fused, cost.collective, chips,
                    model_flops=model_flops_per_chip, spec=spec)


def memory_summary(compiled) -> Dict[str, float]:
    ma = compiled.memory_analysis()
    if ma is None:
        return {}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes"):
        out[k] = float(getattr(ma, k, 0.0))
    args = out.get("argument_size_in_bytes", 0.0)
    alias = out.get("alias_size_in_bytes", 0.0)
    out["peak_bytes_per_device"] = (args - alias) + out.get(
        "output_size_in_bytes", 0.0) + out.get("temp_size_in_bytes", 0.0)
    return out
