"""Arithmetic shared by the metric readers in ``bench/metrics``.

A reader is ``read(run) -> float | None``; ``run`` is the ``Run`` that
``bench/run.py`` assembles. A reader that finds nothing to read returns
None, and the harness leaves its metric out of the line.
"""
from __future__ import annotations

from typing import Optional

from benchlib import counts, trace
from benchlib.peaks import matmul_peak


def host_ms_per_tick(run) -> Optional[float]:
    """Host time of each ``engine.step()`` span that no device op covers,
    averaged over the window's ticks."""
    red = run.reduced
    if red is None or not red.busy:
        return None
    host, n = trace.self_time(red, "bench.step")
    return host / n * 1e3 if n else None


def tick_device_ms(run) -> Optional[float]:
    red = run.reduced
    n = len(run.window.ticks)
    if red is None or not red.busy or not n:
        return None
    return red.busy_s / n * 1e3


def idle_share(run) -> Optional[float]:
    red = run.reduced
    if red is None or not red.busy:
        return None
    return (1.0 - red.busy_s / red.window_s) * 100.0


def rows(tick):
    return [(int(p), int(c)) for p, c in zip(tick.pos0, tick.counts) if c]


def paged_attn_roofline(run) -> Optional[float]:
    """Roofline time of what each row's own context needs, over the device
    time of the ``paged_attention*`` kernels. One kernel call per layer and
    tick covers every row; the roofline is summed over the layers."""
    red = run.reduced
    if red is None:
        return None
    kernel = red.kernel_time("paged_attention")
    if kernel <= 0:
        return None
    groups = run.geometry.groups()
    peak_f, bw = run.peaks["bf16_flops_per_s"], run.peaks["hbm_bytes_per_s"]
    best, bound = 0.0, {}
    for tk in run.window.ticks:
        tick_rows = rows(tk)
        for layer, n in groups:
            fl = by = 0.0
            for p, c in tick_rows:
                f, b = counts.attn_row_cost(p, c, layer, run.layout)
                fl, by = fl + f, by + b
            if fl or by:
                t, which = counts.roofline_s(fl, by, peak_f, bw)
                best += t * n
                bound[which] = bound.get(which, 0) + n
    run.notes["paged_attn_bound"] = bound
    return best / kernel * 100.0


def int8_matmul_roofline(run) -> Optional[float]:
    """Roofline time of each dense linear (``per_token`` 1) at M = real
    tokens fed, over the device time of the ``int8_matmul*`` kernels.
    Routed experts are left to a metric that reads their routing."""
    red = run.reduced
    if red is None or run.precision != "w8a8":
        return None
    kernel = red.kernel_time("int8_matmul")
    if kernel <= 0:
        return None
    dense = [(lin, n) for layer, n in run.geometry.groups()
             for lin in layer.linears if lin.per_token == 1]
    peak_i, bw = run.peaks["int8_ops_per_s"], run.peaks["hbm_bytes_per_s"]
    best, bound = 0.0, {}
    for tk in run.window.ticks:
        if not tk.fed:
            continue
        for lin, n in dense:
            ops, by = counts.int8_matmul_cost(tk.fed, lin.k, lin.n)
            t, which = counts.roofline_s(ops, by, peak_i, bw)
            best += t * n
            bound[which] = bound.get(which, 0) + n
    run.notes["int8_matmul_bound"] = bound
    return best / kernel * 100.0


def mfu(run) -> Optional[float]:
    """Model operations the fed tokens need, per second of the traced
    window, over the peak of the configuration's matmul dtype."""
    red = run.reduced
    if red is None or not red.busy:
        return None
    ops = sum(counts.tick_model_ops(rows(tk), tk.banked, run.geometry)
              for tk in run.window.ticks)
    if ops <= 0:
        return None
    return ops / red.window_s / matmul_peak(run.peaks, run.precision) * 100.0
