"""From the profiler's trace to the record that the metric readers read.

``extract`` turns an ``.xplane.pb`` into plain lists: every event of the
device planes, and the benchmark's own host spans (``chipbench.*``) with
the program's jitted dispatches (``PjitFunction(...)``) from the host
planes.  ``reduce`` cuts that to the traced window, the host span
``chipbench.window``, and sums per device: busy time (the union of the op
intervals, leaving out loops and calls whose span holds other ops), time
per op name, the busy time inside each XLA module's executions, and the
idle gaps.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Dict, List, Optional

WINDOW = "chipbench.window"
SPAN_PREFIX = "chipbench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# ops whose span holds other ops of the same line
CONTAINERS = ("while", "conditional", "call")
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_SUFFIX = re.compile(r"\(\d+\)$")


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def op_kind(name: str) -> str:
    """``fusion.12`` -> ``fusion``."""
    return re.sub(r"(\.\d+)+$", "", name)


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _keep_host(name: str) -> bool:
    return name.startswith(SPAN_PREFIX) or name.startswith("PjitFunction")


def extract(path: str) -> dict:
    """``{"planes": [{"name", "lines": [{"name", "events": [[name,
    start_ns, dur_ns], ...]}]}]}`` of the device planes and the kept host
    events."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in line.events
                      if device or _keep_host(e.name)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def save(raw: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(raw, f, separators=(",", ":"))


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def union(intervals: List[List[float]]) -> List[List[float]]:
    """Merge ``[start, end]`` intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def overlap(a: List[List[float]], b: List[List[float]]) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _clip(events, t0: float, t1: float):
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            yield name, a, b


def _line(plane: dict, name: str) -> List[list]:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def window_span(raw: dict) -> Optional[List[float]]:
    """``[start_ns, end_ns]`` of the last ``chipbench.window`` host span."""
    spans = [[s, s + d] for p in raw["planes"] if not _DEVICE.match(p["name"])
             for line in p["lines"] for n, s, d in line["events"]
             if n == WINDOW]
    return max(spans) if spans else None


def _host_spans(raw: dict, t0: float, t1: float) -> List[list]:
    return [[n, a, b] for p in raw["planes"] if not _DEVICE.match(p["name"])
            for line in p["lines"] for n, a, b in _clip(line["events"], t0, t1)
            if n != WINDOW]


def _label(spans: List[list], mid: float) -> str:
    """The innermost (shortest) host span covering ``mid``."""
    covering = [s for s in spans if s[1] <= mid <= s[2]]
    if not covering:
        return "(no host span)"
    return min(covering, key=lambda s: s[2] - s[1])[0]


def reduce(raw: dict, devices: Optional[List[int]] = None) -> dict:
    """The record of the traced window.  ``devices`` limits it to those
    TPU ids (default: every TPU plane that ran an op in the window)."""
    span = window_span(raw)
    if span is None:
        raise ValueError(f"the trace holds no {WINDOW!r} host span")
    t0, t1 = span
    hosts = _host_spans(raw, t0, t1)
    per_device = []
    for plane in raw["planes"]:
        m = _DEVICE.match(plane["name"])
        if not m or (devices is not None and int(m.group(1)) not in devices):
            continue
        ops = [(op_name(n), a, b)
               for n, a, b in _clip(_line(plane, OPS_LINE), t0, t1)]
        ops = [o for o in ops if op_kind(o[0]) not in CONTAINERS]
        if not ops:
            continue
        busy = union([[a, b] for _, a, b in ops])
        op_s: Dict[str, float] = {}
        for name, a, b in ops:
            op_s[name] = op_s.get(name, 0.0) + (b - a) * 1e-9
        spans: Dict[str, List[List[float]]] = {}
        for name, a, b in _clip(_line(plane, MODULES_LINE), t0, t1):
            spans.setdefault(_SUFFIX.sub("", name), []).append([a, b])
        module_s = {name: overlap(busy, union(ivs)) * 1e-9
                    for name, ivs in spans.items()}
        edges = [t0] + [x for iv in busy for x in iv] + [t1]
        gaps = [[edges[i], edges[i + 1]] for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        per_device.append(dict(
            id=int(m.group(1)),
            busy_s=sum(b - a for a, b in busy) * 1e-9,
            op_s=op_s, module_s=module_s,
            gaps=[[_label(hosts, (a + b) / 2), (b - a) * 1e-9]
                  for a, b in gaps]))
    per_device.sort(key=lambda d: d["id"])
    busy = (sum(d["busy_s"] for d in per_device) / len(per_device)
            if per_device else 0.0)
    return dict(window_s=(t1 - t0) * 1e-9, devices=per_device, busy_s=busy)


def breakdown(record: dict, top: int = 10) -> dict:
    """The device ops that took most time and the longest idle gaps, on
    the first device, each ``[name, seconds]``."""
    if not record["devices"]:
        return dict(device_ops=[], idle_gaps=[])
    dev = record["devices"][0]
    ops = sorted(dev["op_s"].items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(dev["gaps"], key=lambda g: -g[1])[:top]
    return dict(device_ops=[[n, s] for n, s in ops],
                idle_gaps=[[n, s] for n, s in gaps])
