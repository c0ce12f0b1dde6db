"""CPU tests of the readers of the engine's own spans
(``benchlib.serve_spans``) on synthetic records and a synthetic trace:
the four metrics' values, which ``serve.step`` spans the window takes,
None when they do not match the window's ticks or the program has no
recorder, and the clock offset from the trace's ``bench.step`` spans.
Then one traced cell at ``opt_tiny`` size on the CPU, with the look for a
chip patched inside the test, reports the four metrics."""
import io
import json
import math
import sys
from contextlib import redirect_stdout
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))
sys.path.insert(0, str(BENCH.parent / "src"))

from benchlib import driver, serve_spans, trace  # noqa: E402
from repro.serving import telemetry  # noqa: E402
import tiny  # noqa: E402

OFFSET = 1000.0            # trace clock minus the client's clock
READERS = [serve_spans.attn_grid_fill, serve_spans.prefill_tick_share,
           serve_spans.host_prep_ms, serve_spans.host_apply_ms]


def _tick(ids, tick, t0, shape=None, assemble_ms=3.0, wait_ms=800.0):
    """One ``serve.step`` starting at ``t0`` (client seconds), its phases
    back to back; ``shape`` = (T, width, blocks) of its dispatch, or None
    for a tick that feeds nothing."""
    if shape is None:
        phases = [("serve.retire", 1), ("serve.slo", 1), ("serve.admit", 1),
                  ("serve.plan", 1), ("serve.retire", 1)]
    else:
        phases = [("serve.retire", 1), ("serve.slo", 1), ("serve.admit", 2),
                  ("serve.plan", 1), ("serve.assemble", assemble_ms),
                  ("serve.dispatch", 2), ("serve.wait", wait_ms),
                  ("serve.apply", 4), ("serve.retire", 1)]
    root = next(ids)
    kids, t = [], t0
    for name, ms in phases:
        attrs = {}
        if name == "serve.dispatch":
            T, width, blocks = shape
            attrs = dict(T=T, width=width, B=4, blocks=blocks)
        kids.append(telemetry.Span(next(ids), name, t, t + ms / 1e3, root,
                                   tick, attrs))
        t += ms / 1e3
    return telemetry.Span(root, "serve.step", t0, t, None, tick, {}), kids


def _busy(kids):
    """The device is busy from 0.5 ms into each wait to 0.2 ms before its
    end, on the trace's clock."""
    return [(k.t0 + 5e-4 + OFFSET, k.t1 - 2e-4 + OFFSET)
            for k in kids if k.name == "serve.wait"]


def _world(monkeypatch, extra_ticks=0):
    """A recorder with a tick before the window, four in it (a prefill
    tick, two decode ticks, one that feeds nothing) and one after; the
    window, and the trace of it."""
    ids = iter(range(10_000))
    rec = telemetry.Recorder()
    shapes = [(16, 4, 6), (1, 4, 8), (1, 8, 10), None]
    waits = [800.0, 400.0, 400.0, 0.0]
    t, ticks, busy, marks = 5.0, [], [], []
    step, kids = _tick(ids, 0, t, (16, 2, 2))            # before the window
    rec.spans.extend(kids + [step])
    t_open = 10.0 - 2e-4
    t = 10.0
    for i, (shape, wait) in enumerate(zip(shapes, waits)):
        t0 = t - 1e-4                    # the client's clock, before step()
        step, kids = _tick(ids, i + 1, t, shape,
                           assemble_ms=300.0 if i == 1 else 3.0,
                           wait_ms=wait)
        rec.spans.extend(kids + [step])
        busy += _busy(kids)
        ticks.append(driver.Tick(t0, step.t1 + 1e-4, 3, 3, np.zeros(4),
                                 np.zeros(4)))
        # the trace's bench.step span opens with the tick; one is late
        late = 0.25 if i == 2 else 0.0
        marks.append(("bench.step", t0 + OFFSET + late,
                      step.t1 + 1e-4 + OFFSET))
        t = step.t1 + 1e-3
    step, kids = _tick(ids, 9, t + 1.0, (1, 8, 10))       # after the window
    rec.spans.extend(kids + [step])
    for _ in range(extra_ticks):
        ticks.append(ticks[-1])
    monkeypatch.setattr(telemetry, "RECORDER", rec)
    window = driver.Window(t_open, ticks[-1].t1, ticks)
    lo, hi = t_open + OFFSET, window.t_close + OFFSET
    spans = [("bench.window", lo, hi)] + marks
    red = trace.Reduced(lo, hi, trace.total(busy), {}, busy, spans)
    return SimpleNamespace(window=window, reduced=red, notes={}), rec


def test_window_takes_the_serve_steps_between_its_open_and_close(monkeypatch):
    run, _ = _world(monkeypatch)
    ticks = serve_spans.window(run)
    assert [tk.step.tick for tk in ticks] == [1, 2, 3, 4]
    assert [k.name for k in ticks[0].kids][-3:] == \
        ["serve.wait", "serve.apply", "serve.retire"]
    assert run.notes["tick_shapes"] == [[1, 4, 1], [1, 8, 1], [16, 4, 1]]


def test_the_four_readers_on_synthetic_spans(monkeypatch):
    run, _ = _world(monkeypatch)
    # blocks (6 + 8 + 10) over B x width (4*4 + 4*4 + 4*8)
    assert math.isclose(serve_spans.attn_grid_fill(run), 24 / 64 * 100)
    # device busy 799.3 ms in the T=16 tick, of 799.3 + 2 x 399.3
    assert math.isclose(serve_spans.prefill_tick_share(run),
                        799.3 / 1597.9 * 100)
    # before the first wait: 1+1+2+1+3+2 ms, 1+1+2+1+300+2, again 10,
    # and all 5 ms of the tick that feeds nothing
    assert math.isclose(serve_spans.host_prep_ms(run),
                        (10 + 307 + 10 + 5) / 4)
    # after the last wait: apply 4 + retire 1, none in the idle tick
    assert math.isclose(serve_spans.host_apply_ms(run), (5 + 5 + 5) / 4)


def test_prefill_tick_share_counts_device_time_outside_the_wait(monkeypatch):
    """Device work of a tick that runs while its host still assembles (as
    a dispatched-ahead tick would) is counted to that tick: the share is
    the device's, not the wait's."""
    run, _ = _world(monkeypatch)
    (a,) = serve_spans.window(run)[1].named("serve.assemble")
    lo = a.t0 + OFFSET + 0.1
    run.reduced.busy = sorted(run.reduced.busy + [(lo, lo + 0.1)])
    assert math.isclose(serve_spans.prefill_tick_share(run),
                        799.3 / 1697.9 * 100)


def test_idle_seconds_by_span_on_the_trace_clock(monkeypatch):
    """The late ``bench.step`` mark moves a mean offset, not the median:
    the device-idle seconds land in the spans they belong to."""
    run, _ = _world(monkeypatch)
    serve_spans.window(run)
    idle = run.notes["idle_by_serve_span"]
    assert math.isclose(idle["serve.wait"], 3 * 7e-4, rel_tol=1e-6)
    assert math.isclose(idle["serve.apply"], 3 * 4e-3, rel_tol=1e-6)
    assert math.isclose(idle["serve.assemble"], 0.306, rel_tol=1e-6)
    assert math.isclose(idle["serve.plan"], 4e-3, rel_tol=1e-6)
    gap = run.notes["longest_idle_gap"]
    assert gap["span"] == "serve.assemble" and gap["s"] > 0.3
    over = gap["overlaps"]
    assert math.isclose(over["serve.assemble"], 0.3, rel_tol=1e-6)
    assert math.isclose(over["serve.apply"], 4e-3, rel_tol=1e-6)
    assert over["serve.step"] < gap["s"] and "serve.slo" in over
    per_tick = run.notes["serve_ms_per_tick"]
    assert math.isclose(per_tick["serve.wait"], 1600 / 4)


def test_untraced_run_writes_no_idle_note(monkeypatch):
    run, _ = _world(monkeypatch)
    run.reduced = None
    assert serve_spans.host_prep_ms(run) is not None
    assert serve_spans.prefill_tick_share(run) is None
    assert "tick_shapes" in run.notes
    assert "idle_by_serve_span" not in run.notes


@pytest.mark.parametrize("reader", READERS, ids=lambda r: r.__name__)
def test_none_when_spans_and_ticks_differ(monkeypatch, reader):
    run, _ = _world(monkeypatch, extra_ticks=1)
    assert reader(run) is None
    assert run.notes == {}


@pytest.mark.parametrize("reader", READERS, ids=lambda r: r.__name__)
def test_none_without_the_recorder(monkeypatch, reader):
    """A program without ``repro.serving.telemetry``, as before it."""
    import repro.serving
    run, _ = _world(monkeypatch)
    monkeypatch.delattr(repro.serving, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.serving.telemetry", None)
    assert reader(run) is None
    assert run.notes == {}


def test_traced_cell_reports_the_four_metrics(monkeypatch, tmp_path):
    """The CPU has no device plane, so no idle note and no
    ``prefill_tick_share``, which reads device time; the other three
    metrics and the window's tick shapes come from the program's spans."""
    import run
    from benchlib import peaks
    v5e = peaks.peaks_for("TPU v5 lite")
    monkeypatch.setattr(run, "check_devices",
                        lambda jax, chips: jax.devices()[:chips])
    monkeypatch.setattr(run, "peaks_for", lambda kind: v5e)
    monkeypatch.setattr(run, "use_compile_cache", lambda jax, checkout: None)
    root = tiny.make_root(tmp_path)
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = run.main(["--workload", "tiny.tiny-closed", "--seed",
                       str(2**31 + 123), "--seconds", "1", "--trace", "1"],
                      root=root)
    assert rc == 0
    lines = buf.getvalue().strip().splitlines()
    res, info = json.loads(lines[-1]), json.loads(lines[-2][len("info "):])
    got = {n: m["value"] for n, m in res["metrics"].items()}
    assert {"attn_grid_fill", "host_prep_ms.backlog",
            "host_apply_ms.backlog"} <= set(got)
    assert "prefill_tick_share.backlog" not in got
    assert 0 < got["attn_grid_fill"] <= 100
    assert got["host_prep_ms.backlog"] > 0 and got["host_apply_ms.backlog"] > 0
    assert sum(n for _, _, n in info["tick_shapes"]) == info["ticks"]
    assert "idle_by_serve_span" not in info
