"""Reduction of a profiler trace to device busy time, kernel time and the
host spans that cover idle gaps.

``load(path)`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into
plain tuples; everything after that is arithmetic on intervals, so the
tests feed it synthetic traces. Device operations are the events of the
``XLA Ops`` line of each ``/device:TPU:<n>`` plane; their names are the HLO
instruction text (``%paged_attention.3 = ...``), and ``op_name`` reduces
one to its instruction name without the numeric suffix. Host spans are the
``bench.*`` annotations of the host plane, on the same clock.
"""
from __future__ import annotations

import dataclasses
import glob
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # (start_s, end_s)

_OP = re.compile(r"%?([\w.\-]+?)(?:\.\d+)?\s*=")
_SUFFIX = re.compile(r"\.\d+$")


@dataclasses.dataclass
class Trace:
    # per device plane: list of (op name, start_s, end_s)
    device_ops: Dict[str, List[Tuple[str, float, float]]]
    # host spans: (name, start_s, end_s)
    spans: List[Tuple[str, float, float]]


def op_name(event_name: str) -> str:
    """``%paged_attention.3 = f32[...] custom-call(...)`` -> ``paged_attention``."""
    m = _OP.match(event_name)
    name = m.group(1) if m else event_name.split()[0]
    return _SUFFIX.sub("", name)


def load(trace_dir: Path, span_prefix: str = "bench.") -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    device_ops: Dict[str, List[Tuple[str, float, float]]] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = device_ops.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    s = e.start_ns * 1e-9
                    ops.append((op_name(e.name), s, s + e.duration_ns * 1e-9))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(span_prefix):
                        s = e.start_ns * 1e-9
                        spans.append((e.name, s, s + e.duration_ns * 1e-9))
    return Trace(device_ops, spans)


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping intervals; the result is sorted and disjoint."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def covered(busy: Sequence[Interval], lo: float, hi: float) -> float:
    """Seconds of ``[lo, hi]`` that the disjoint ``busy`` intervals cover."""
    return total(clip(busy, lo, hi))


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """Idle intervals of ``[lo, hi]`` between the disjoint busy intervals."""
    out, t = [], lo
    for s, e in clip(busy, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


@dataclasses.dataclass
class Reduced:
    """What the per-layer readers take from one traced window."""
    lo: float
    hi: float
    busy_s: float                         # mean over the chips used
    kernel_s: Dict[str, float]            # op name -> device seconds
    busy: List[Interval]                  # first chip's busy union
    spans: List[Tuple[str, float, float]]

    @property
    def window_s(self) -> float:
        return self.hi - self.lo

    def kernel_time(self, prefix: str) -> float:
        return sum(v for k, v in self.kernel_s.items() if k.startswith(prefix))


def reduce(trace: Trace, lo: float, hi: float) -> Reduced:
    """Busy union, busy seconds and per-op device seconds inside [lo, hi]."""
    planes = sorted(trace.device_ops)
    busy_per_chip, kernel_s = [], {}
    first_busy: List[Interval] = []
    for i, p in enumerate(planes):
        ops = trace.device_ops[p]
        b = union(clip([(s, e) for _, s, e in ops], lo, hi))
        busy_per_chip.append(total(b))
        if i == 0:
            first_busy = b
        for name, s, e in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                kernel_s[name] = kernel_s.get(name, 0.0) + d
    busy_s = sum(busy_per_chip) / len(busy_per_chip) if planes else 0.0
    spans = [(n, s, e) for n, s, e in trace.spans if e > lo and s < hi]
    return Reduced(lo, hi, busy_s, kernel_s, first_busy, spans)


def span_window(trace: Trace, name: str) -> Optional[Interval]:
    """Start and end of the (first) host span called ``name``."""
    for n, s, e in trace.spans:
        if n == name:
            return s, e
    return None


def span_at(spans: Sequence[Tuple[str, float, float]], t: float,
            exclude: Tuple[str, ...] = ()) -> str:
    """Name of the shortest host span that contains instant ``t``."""
    best, best_len = "none", float("inf")
    for n, s, e in spans:
        if n in exclude:
            continue
        if s <= t <= e and e - s < best_len:
            best, best_len = n, e - s
    return best


def breakdown(red: Reduced, top: int = 10,
              exclude: Tuple[str, ...] = ("bench.window",)) -> dict:
    """The device ops that took most time, and the longest idle gaps of the
    window, each gap named by the host span that covered its middle."""
    ops = sorted(red.kernel_s.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(gaps(red.busy, red.lo, red.hi),
                  key=lambda g: g[0] - g[1])[:top]
    return {
        "device_ops": [[n, s] for n, s in ops],
        "idle_gaps": [[span_at(red.spans, (s + e) / 2, exclude), e - s]
                      for s, e in idle],
    }


def self_time(red: Reduced, span: str) -> Tuple[float, int]:
    """Host time of the spans called ``span`` that no device op covers,
    and how many such spans lie inside the window."""
    host, n = 0.0, 0
    for name, s, e in red.spans:
        if name != span:
            continue
        n += 1
        host += (e - s) - covered(red.busy, s, e)
    return host, n
