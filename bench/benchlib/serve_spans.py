"""The engine's own host spans over the window, and the readers of them.

The program keeps a ``serve.step`` span for every ``engine.step()`` in
``repro.serving.telemetry.RECORDER``, with the tick's phases as children
(``serve.retire``, ``serve.slo``, ``serve.admit``, ``serve.plan``,
``serve.assemble``, ``serve.dispatch``, ``serve.wait``, ``serve.apply``)
and the tick's shape as counters on ``serve.dispatch`` (``T``, ``width``,
``B``, ``blocks``). They are on ``time.perf_counter()``, the client's
clock. ``window(run)`` takes the ``serve.step`` spans inside the window;
a program without the recorder, or a window whose ticks they do not match
one for one, gives None, and so does every reader here.
``prefill_tick_share`` reads the device's busy time from the trace inside
those spans, so it is None untraced too; the other readers take the
spans' own times and counters.

It also writes notes: ``tick_shapes``, the window's ticks by
``[T, width, ticks]``; ``serve_ms_per_tick``, each span's milliseconds per
tick; and, in a traced run, ``idle_by_serve_span``, the device-idle
seconds inside each span, and ``longest_idle_gap``: the window's longest
idle gap, the shortest span around its middle and the gap's seconds
inside each span. The spans are put on the trace's clock by the median
offset between each tick's ``bench.step`` span in the trace and the
tick's start.
"""
from __future__ import annotations

import bisect
import dataclasses
import statistics
from typing import Dict, List, Optional, Sequence, Tuple

from benchlib import trace


@dataclasses.dataclass
class ServeTick:
    step: object               # the serve.step span
    kids: List                 # its children, in the order they opened

    def named(self, name: str) -> List:
        return [k for k in self.kids if k.name == name]


def window(run) -> Optional[List[ServeTick]]:
    """The window's ticks as the program recorded them, or None."""
    try:
        from repro.serving import telemetry
    except ImportError:
        return None
    w = run.window
    spans = list(telemetry.RECORDER.spans)
    steps = sorted((s for s in spans if s.name == "serve.step"
                    and w.t_open <= s.t0 and s.t1 <= w.t_close),
                   key=lambda s: s.t0)
    if not steps or len(steps) != len(w.ticks):
        return None
    at = {s.id: i for i, s in enumerate(steps)}
    kids: List[List] = [[] for _ in steps]
    for s in spans:
        if s.parent in at:
            kids[at[s.parent]].append(s)
    ticks = [ServeTick(st, sorted(k, key=lambda s: s.id))
             for st, k in zip(steps, kids)]
    if "tick_shapes" not in run.notes:       # the first reader writes them
        _note(run, ticks)
    return ticks


def _note(run, ticks: List[ServeTick]) -> None:
    shapes: Dict[Tuple[int, int], int] = {}
    for tk in ticks:
        for d in tk.named("serve.dispatch"):
            key = (d.attrs["T"], d.attrs["width"])
            shapes[key] = shapes.get(key, 0) + 1
    run.notes["tick_shapes"] = [[t, w, n] for (t, w), n in sorted(
        shapes.items(), key=lambda kv: (kv[0][0], kv[0][1] or 0))]
    spans = [tk.step for tk in ticks] + [k for tk in ticks for k in tk.kids]
    per: Dict[str, float] = {}
    for s in spans:
        per[s.name] = per.get(s.name, 0.0) + s.t1 - s.t0
    run.notes["serve_ms_per_tick"] = {
        n: v / len(ticks) * 1e3 for n, v in sorted(per.items())}
    off = _offset(run)
    if off is None:
        return
    red = run.reduced
    starts = [s for s, _ in red.busy]
    idle: Dict[str, float] = {}
    placed = []
    for s in spans:
        lo, hi = max(s.t0 + off, red.lo), min(s.t1 + off, red.hi)
        if hi > lo:
            idle[s.name] = idle.get(s.name, 0.0) + \
                (hi - lo) - _busy_in(red.busy, starts, lo, hi)
            placed.append((s.name, lo, hi))
    run.notes["idle_by_serve_span"] = dict(sorted(idle.items()))
    gaps = trace.gaps(red.busy, red.lo, red.hi)
    if gaps:
        s, e = max(gaps, key=lambda g: g[1] - g[0])
        over: Dict[str, float] = {}
        for n, lo, hi in placed:
            if hi > s and lo < e:
                over[n] = over.get(n, 0.0) + min(hi, e) - max(lo, s)
        run.notes["longest_idle_gap"] = {
            "s": e - s, "span": trace.span_at(placed, (s + e) / 2),
            "overlaps": dict(sorted(over.items()))}


def _offset(run) -> Optional[float]:
    """Trace clock minus the client's clock, or None untraced."""
    red = run.reduced
    if red is None or not red.busy:
        return None
    marks = sorted(s for n, s, _ in red.spans if n == "bench.step")
    if len(marks) != len(run.window.ticks):
        return None
    return statistics.median(m - tk.t0 for m, tk in zip(marks,
                                                        run.window.ticks))


def _busy_in(busy: Sequence[Tuple[float, float]], starts: Sequence[float],
             lo: float, hi: float) -> float:
    """Seconds of [lo, hi] inside the sorted, disjoint ``busy``."""
    i = max(bisect.bisect_right(starts, lo) - 1, 0)
    return trace.covered(busy[i:bisect.bisect_left(starts, hi)], lo, hi)


def attn_grid_fill(run) -> Optional[float]:
    """Table entries the fed rows hold (``blocks``) over the paged-attention
    grid's steps per head (``B`` x ``width``), summed over the window's
    dispatches: the share of grid steps that read a real block."""
    ticks = window(run)
    if ticks is None:
        return None
    held = grid = 0
    for tk in ticks:
        for d in tk.named("serve.dispatch"):
            if d.attrs["width"]:
                held += d.attrs["blocks"]
                grid += d.attrs["B"] * d.attrs["width"]
    return held / grid * 100.0 if grid else None


def prefill_tick_share(run) -> Optional[float]:
    """Share of the device-busy time inside the window's ``serve.step``
    spans that falls in ticks which fed more than one token to some row
    (``T`` > 1). The spans are put on the trace's clock; untraced, None."""
    ticks = window(run)
    off = None if ticks is None else _offset(run)
    if off is None:
        return None
    busy = run.reduced.busy
    starts = [s for s, _ in busy]
    total = prefill = 0.0
    for tk in ticks:
        b = _busy_in(busy, starts, tk.step.t0 + off, tk.step.t1 + off)
        total += b
        if max((d.attrs["T"] for d in tk.named("serve.dispatch")),
               default=0) > 1:
            prefill += b
    return prefill / total * 100.0 if total else None


def host_prep_ms(run) -> Optional[float]:
    """Milliseconds of each ``serve.step`` before its first ``serve.wait``
    (all of it in a tick that waits for nothing), averaged over ticks."""
    ticks = window(run)
    if ticks is None:
        return None
    prep = [(w[0].t0 if w else tk.step.t1) - tk.step.t0
            for tk in ticks for w in [tk.named("serve.wait")]]
    return statistics.fmean(prep) * 1e3


def host_apply_ms(run) -> Optional[float]:
    """Milliseconds of each ``serve.step`` after its last ``serve.wait``
    (none in a tick that waits for nothing), averaged over ticks."""
    ticks = window(run)
    if ticks is None:
        return None
    after = [tk.step.t1 - w[-1].t1 if w else 0.0
             for tk in ticks for w in [tk.named("serve.wait")]]
    return statistics.fmean(after) * 1e3
