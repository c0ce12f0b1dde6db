"""Host spans of the serving engine (``repro.serving.telemetry``): one
``serve.step`` per ``step()`` with its phases nested in order, the
counters on ``serve.dispatch`` checked against what the tick was given,
the ring's bound, and the spans in a ``jax.profiler`` trace."""
import dataclasses
import glob
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs.paper_models import opt_tiny
from repro.kernels.paged_attention import blocks_per_step, live_steps
from repro.models import model_init
from repro.serving import ContinuousBatcher, Request, telemetry

TICK = ["serve.retire", "serve.slo", "serve.admit", "serve.plan",
        "serve.assemble", "serve.dispatch", "serve.wait", "serve.apply",
        "serve.retire"]
IDLE = ["serve.retire", "serve.slo", "serve.admit", "serve.plan",
        "serve.retire"]


def _engine():
    cfg = dataclasses.replace(opt_tiny(vocab=64, seq_len=32), max_seq_len=64)
    params = model_init(jax.random.PRNGKey(0), cfg)
    eng = ContinuousBatcher(params, cfg, batch_size=4, max_len=64,
                            paged=True, block_size=8, num_blocks=64,
                            token_budget=16)
    rng = np.random.default_rng(0)
    for uid, (plen, out) in enumerate([(11, 6), (3, 9), (20, 4)]):
        eng.submit(Request(uid=uid, prompt=rng.integers(1, 64, plen),
                           max_new_tokens=out))
    return eng


def _tap(eng):
    """Wrap the engine's tick and live width to record, per call, what the
    tick was given: counts, T, the width, the blocks of the fed rows, the
    rows' positions and their held blocks."""
    calls, widths = [], []
    step_fn, live_width = eng._step_fn, eng._live_width

    def width():
        widths.append(live_width())
        return widths[-1]

    def tick(params, cache, tokens, pos, counts, keys, w, ws):
        fed = np.flatnonzero(np.asarray(counts))
        calls.append(dict(counts=np.asarray(counts), T=tokens.shape[1],
                          width=w, last_width=widths[-1],
                          blocks=sum(len(eng.slots[i].blocks) for i in fed),
                          pos=np.asarray(pos), held=np.asarray(ws)))
        return step_fn(params, cache, tokens, pos, counts, keys, w, ws)

    eng._live_width, eng._step_fn = width, tick
    return calls


@pytest.fixture(scope="module")
def ticks():
    """Each step of a short paged run and one idle step after it: the
    spans it left, the tick's given inputs and the engine's counters."""
    eng = _engine()
    rec = eng.telemetry
    calls = _tap(eng)
    out = []
    for _ in range(40):
        busy = bool(eng.queue or any(s.req for s in eng.slots))
        last = max((s.id for s in rec.spans), default=-1)
        n_calls = len(calls)
        eng.step()
        new = [s for s in rec.spans if s.id > last]
        out.append(dict(spans=new, tick=eng.ticks, engine=eng.engine_id,
                        call=calls[-1] if len(calls) > n_calls else None,
                        tokens=eng.last_tick_tokens))
        if not busy:
            break
    assert out[-1]["call"] is None and len(out) > 3
    return out


def _tree(spans):
    roots = [s for s in spans if s.name == "serve.step"]
    assert len(roots) == 1
    root = roots[0]
    kids = sorted((s for s in spans if s.parent == root.id),
                  key=lambda s: s.id)
    assert len(kids) == len(spans) - 1
    return root, kids


def test_each_step_leaves_one_serve_step_with_its_phases_in_order(ticks):
    for tk in ticks:
        root, kids = _tree(tk["spans"])
        assert [k.name for k in kids] == (TICK if tk["call"] else IDLE)
        assert root.parent is None and root.tick == tk["tick"]
        assert root.attrs == {"engine": tk["engine"]}
        assert all(k.tick == root.tick for k in kids)
        t = root.t0
        for k in kids:
            assert t <= k.t0 <= k.t1 <= root.t1
            t = k.t1


def test_dispatch_counters_match_what_the_tick_was_given(ticks):
    fed = [tk for tk in ticks if tk["call"]]
    assert any(tk["call"]["T"] > 1 for tk in fed)      # prefill ticks too
    for tk in fed:
        _, kids = _tree(tk["spans"])
        (d,) = [k for k in kids if k.name == "serve.dispatch"]
        call = tk["call"]
        assert tk["tokens"] == call["counts"].sum()
        assert d.attrs["width"] == call["width"] == call["last_width"]
        assert d.attrs["blocks"] == call["blocks"] > 0
        assert d.attrs["T"] == call["T"] and d.attrs["B"] == 4
        assert set(d.attrs) == {"T", "width", "B", "blocks", "steps", "grid"}


def test_dispatch_attention_grid_matches_the_kernels_step_function(ticks):
    """``steps`` and ``grid`` on ``serve.dispatch`` are what the paged
    kernel's own step function gives for the tick's inputs: the steps that
    fetch, summed over rows, and B x ceil(width / P)."""
    seen = []
    for tk in ticks:
        if not tk["call"]:
            continue
        _, kids = _tree(tk["spans"])
        (d,) = [k for k in kids if k.name == "serve.dispatch"]
        call = tk["call"]
        w = call["width"]
        want = live_steps(call["pos"], call["T"], call["held"], block_size=8,
                          width=w)
        assert d.attrs["steps"] == int(want.sum()) > 0
        assert d.attrs["grid"] == 4 * -(-w // blocks_per_step(8, w))
        seen.append((d.attrs["steps"], d.attrs["grid"]))
    # slots that hold nothing fetch nothing: some tick uses part of its grid
    assert all(s <= g for s, g in seen) and any(s < g for s, g in seen)


def test_engines_of_one_process_are_told_apart():
    a, b = _engine(), _engine()
    assert a.telemetry is b.telemetry is telemetry.RECORDER
    assert b.engine_id > a.engine_id
    a.step()
    b.step()
    steps = [s for s in telemetry.RECORDER.spans if s.name == "serve.step"]
    assert [s.attrs["engine"] for s in steps[-2:]] == [a.engine_id,
                                                        b.engine_id]
    assert [s.tick for s in steps[-2:]] == [1, 1]


def test_ring_keeps_the_newest_spans_at_its_bound():
    rec = telemetry.Recorder(maxlen=8)
    for i in range(5):
        with rec.span("outer", tick=i):
            for _ in range(3):
                with rec.span("inner", n=i):
                    pass
    assert len(rec.spans) == 8 and rec.spans.maxlen == 8
    # appended as they close: each parent after its three children
    assert [s.id for s in rec.spans] == [13, 14, 15, 12, 17, 18, 19, 16]
    assert rec.spans[-1].name == "outer" and rec.spans[-1].tick == 4
    inner = [s for s in rec.spans if s.name == "inner"]
    assert all(s.parent == rec.spans[-1].id for s in inner[-3:])
    assert all(s.tick == 4 and s.attrs == {"n": 4} for s in inner[-3:])


def test_a_span_that_raises_is_still_recorded_and_closed():
    rec = telemetry.Recorder()
    with pytest.raises(ValueError):
        with rec.span("outer", tick=1):
            with rec.span("inner"):
                raise ValueError
    with rec.span("after"):
        pass
    assert [s.name for s in rec.spans] == ["inner", "outer", "after"]
    assert rec.spans[-1].parent is None and rec.spans[-1].tick is None


def test_serve_spans_appear_in_a_profiler_trace(tmp_path):
    from jax.profiler import ProfileData

    eng = _engine()
    eng.step()                                  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.step()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(Path(tmp_path) / "**" / "*.xplane.pb"),
                        recursive=True)
    names = {e.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events}
    assert set(TICK) | {"serve.step"} <= names
