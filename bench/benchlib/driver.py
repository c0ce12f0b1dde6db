"""Drives the serving engine under one traffic mix and times it from the
client's side.

The engine is used only through ``submit``, ``step(now=)``, ``queue``,
``slots``, ``done``, ``failed``, ``last_counts``, ``last_tick_tokens`` and
``last_tick_new_tokens``; the shape warm-up also calls the engine's jitted
tick (``_step_fn``) on its own ``params`` and ``cache`` with rows that
write nothing. Every host phase of the benchmark is a profiler span:
``bench.submit``, ``bench.step``, ``bench.client``, and ``bench.window``
around the measured window.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Iterator, List

import numpy as np

from benchlib.traffic import Draw, midway, midway_shares


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Tick:
    t0: float
    t1: float
    fed: int                  # tokens fed to the tick (last_tick_tokens)
    banked: int               # tokens banked to requests (last_tick_new_tokens)
    pos0: np.ndarray          # (B,) each row's position before the tick
    counts: np.ndarray        # (B,) tokens each row fed


@dataclasses.dataclass
class Tracked:
    req: object
    submitted: float
    times: List[float] = dataclasses.field(default_factory=list)


class Client:
    """Submits requests and records, after each step, the wall time at
    which each request's output grew."""

    def __init__(self, engine, request_cls, clock: Callable[[], float],
                 first_uid: int = 0):
        self.engine = engine
        self.request_cls = request_cls
        self.clock = clock
        self.tracked: Dict[int, Tracked] = {}
        self._done_seen = len(engine.done)
        self._uid = first_uid

    def submit(self, draw: Draw) -> Tracked:
        with span("bench.submit"):
            req = self.request_cls(uid=self._uid, prompt=draw.prompt,
                                   max_new_tokens=draw.max_new_tokens)
            self._uid += 1
            self.engine.submit(req)
            t = Tracked(req, self.clock())
            self.tracked[req.uid] = t
        return t

    def step(self) -> Tick:
        e = self.engine
        pos0 = np.array([s.pos if s.req is not None else 0 for s in e.slots],
                        np.int64)
        with span("bench.step"):
            t0 = self.clock()
            e.step(now=t0)
            t1 = self.clock()
        with span("bench.client"):
            fed = int(e.last_tick_tokens)
            counts = np.asarray(e.last_counts, np.int64) if fed \
                else np.zeros_like(pos0)
            tick = Tick(t0, t1, fed, int(e.last_tick_new_tokens), pos0,
                        counts)
            self._observe(t1)
        return tick

    def _grew(self, uid: int, n: int, t: float) -> None:
        tr = self.tracked[uid]
        tr.times.extend([t] * (n - len(tr.times)))

    def _observe(self, t: float) -> None:
        for s in self.engine.slots:
            if s.req is not None and len(s.generated) > \
                    len(self.tracked[s.req.uid].times):
                self._grew(s.req.uid, len(s.generated), t)
        done = self.engine.done
        for r in done[self._done_seen:]:
            self._grew(r.uid, len(r.output), t)
        self._done_seen = len(done)

    def newly_done(self, since: int) -> List:
        return self.engine.done[since:]


def warm_shapes(engine, batch: int, shapes) -> None:
    """Run the engine's tick once at each (T, live_width) with rows that
    feed nothing, so each program is compiled (or loaded from the
    persistent cache) before the window. The returned cache is dropped."""
    import jax
    import jax.numpy as jnp

    keys = jnp.asarray(np.zeros((batch, 2), np.uint32))
    zeros = jnp.asarray(np.zeros((batch,), np.int32))
    for t, w in shapes:
        out = engine._step_fn(engine.params, engine.cache,
                              jnp.asarray(np.zeros((batch, t), np.int32)),
                              zeros, zeros, keys, w, zeros)
        jax.block_until_ready(out)
        del out


def pow2_upto(n: int) -> List[int]:
    out, v = [], 1
    while v < n:
        out.append(v)
        v *= 2
    return out + [v]


@dataclasses.dataclass
class Window:
    t_open: float
    t_close: float                 # end of the last tick counted
    ticks: List[Tick]

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open


def closed_loop(client: Client, mix: dict, draws: Iterator[Draw],
                seconds: float, batch: int, on_open: Callable,
                on_close: Callable) -> Window:
    """``clients`` callers; every completion is answered at once with the
    caller's next request. The first ``batch`` requests are caught midway
    (``traffic.midway``) and sent longest first, so the live width reaches
    the window's within the first ticks, the same way for every seed; the
    rest queue fresh. Set-up runs until every one of the first ``batch``
    has its first token, when the batch holds the long-run mix of
    contexts; the window then runs whole ticks for ``seconds``."""
    shares = midway_shares(int(mix["strata"]))
    firsts = [next(draws) for _ in range(mix["clients"])]
    leads = sorted((midway(d, shares[d.stratum]) for d in firsts[:batch]),
                   key=lambda d: (-len(d.prompt), d.stratum))
    lead = [client.submit(d).req.uid for d in leads]
    for d in firsts[batch:]:
        client.submit(d)
    done_seen = 0

    def tick() -> Tick:
        nonlocal done_seen
        tk = client.step()
        fresh = client.newly_done(done_seen)
        done_seen += len(fresh)
        for _ in fresh:
            client.submit(next(draws))
        return tk

    while not all(client.tracked[u].times for u in lead):
        tick()
    ticks: List[Tick] = []
    on_open()               # starts the trace, so it sees the window's span
    with span("bench.window"):
        t_open = client.clock()
        while client.clock() - t_open < seconds:
            ticks.append(tick())
        t_close = ticks[-1].t1
    on_close()
    return Window(t_open, t_close, ticks)
