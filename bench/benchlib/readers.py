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
    tick covers every row."""
    red = run.reduced
    if red is None:
        return None
    kernel = red.kernel_time("paged_attention")
    if kernel <= 0:
        return None
    layers = counts.dims(run.model)["layers"]
    peak_f, bw = run.peaks["bf16_flops_per_s"], run.peaks["hbm_bytes_per_s"]
    best, bound = 0.0, {}
    for tk in run.window.ticks:
        fl = by = 0.0
        for p, c in rows(tk):
            f, b = counts.attn_row_cost(p, c, run.model, run.layout)
            fl, by = fl + f, by + b
        if fl or by:
            t, which = counts.roofline_s(fl, by, peak_f, bw)
            best += t * layers
            bound[which] = bound.get(which, 0) + 1
    run.notes["paged_attn_bound"] = bound
    return best / kernel * 100.0


def int8_matmul_roofline(run) -> Optional[float]:
    """Roofline time of each linear layer at M = real tokens fed, over the
    device time of the ``int8_matmul*`` kernels."""
    red = run.reduced
    if red is None or run.precision != "w8a8":
        return None
    kernel = red.kernel_time("int8_matmul")
    if kernel <= 0:
        return None
    layers = counts.dims(run.model)["layers"]
    peak_i, bw = run.peaks["int8_ops_per_s"], run.peaks["hbm_bytes_per_s"]
    best, bound = 0.0, {}
    for tk in run.window.ticks:
        if not tk.fed:
            continue
        for k, n in counts.linear_shapes(run.model):
            ops, by = counts.int8_matmul_cost(tk.fed, k, n)
            t, which = counts.roofline_s(ops, by, peak_i, bw)
            best += t * layers
            bound[which] = bound.get(which, 0) + layers
    run.notes["int8_matmul_bound"] = bound
    return best / kernel * 100.0


def mfu(run) -> Optional[float]:
    """Model operations the fed tokens need, per second of the traced
    window, over the peak of the configuration's matmul dtype."""
    red = run.reduced
    if red is None or not red.busy:
        return None
    ops = sum(counts.tick_model_ops(rows(tk), tk.banked, run.model)
              for tk in run.window.ticks)
    if ops <= 0:
        return None
    return ops / red.window_s / matmul_peak(run.peaks, run.precision) * 100.0
