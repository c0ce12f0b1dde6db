"""Window arithmetic of the closed loop, on a fake engine and a fake
clock: which ticks a window counts, how the batch opens, and what the
end-to-end reader makes of it."""
import dataclasses
import math
import sys
from pathlib import Path
from types import SimpleNamespace
from typing import List, Optional

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchlib import driver, traffic  # noqa: E402

DT = 0.125        # seconds per fake tick (exact in binary)


class Clock:
    def __init__(self):
        self.t = 64.0

    def __call__(self):
        return self.t


@dataclasses.dataclass(eq=False)
class Req:
    uid: int
    prompt: np.ndarray
    max_new_tokens: int
    output: Optional[np.ndarray] = None
    status: str = "queued"


@dataclasses.dataclass
class Slot:
    req: Optional[Req] = None
    pos: int = 0
    generated: List[int] = dataclasses.field(default_factory=list)
    prefilled: bool = False


class FakeEngine:
    """Admits FIFO into ``b`` slots; a tick prefills a whole prompt (first
    token included) or decodes one token per row, and takes DT seconds."""

    def __init__(self, b, clock):
        self.slots = [Slot() for _ in range(b)]
        self.queue, self.done, self.failed = [], [], []
        self.clock = clock
        self.last_counts = np.zeros(b, np.int64)
        self.last_tick_tokens = self.last_tick_new_tokens = 0

    def submit(self, req):
        self.queue.append(req)

    def step(self, now=None):
        for i, s in enumerate(self.slots):
            if s.req is None and self.queue:
                self.slots[i] = Slot(self.queue.pop(0))
        counts = np.zeros(len(self.slots), np.int64)
        banked = 0
        for i, s in enumerate(self.slots):
            if s.req is None:
                continue
            counts[i] = 1 if s.prefilled else len(s.req.prompt)
            s.pos += int(counts[i])
            s.prefilled = True
            s.generated.append(7)
            banked += 1
        self.clock.t += DT
        self.last_counts = counts
        self.last_tick_tokens, self.last_tick_new_tokens = int(counts.sum()), banked
        for i, s in enumerate(self.slots):
            if s.req is not None and len(s.generated) == s.req.max_new_tokens:
                s.req.output = np.asarray(s.generated)
                s.req.status = "done"
                self.done.append(s.req)
                self.slots[i] = Slot()
        return int((counts > 0).sum())


def _run(window):
    return SimpleNamespace(window=window, notes={})


MIX_CLOSED = {"loop": "closed", "clients": 8,
              "prompt_tokens": {"median": 8, "sigma": 0.0, "min": 8, "max": 8},
              "output_tokens": {"median": 5, "sigma": 0.0, "min": 5, "max": 5},
              "max_total_tokens": 100, "strata": 4}


def test_closed_loop_counts_whole_ticks_over_the_window():
    clock = Clock()
    eng = FakeEngine(4, clock)
    client = driver.Client(eng, Req, clock)
    draws = traffic.stream(MIX_CLOSED, 3, 50)
    calls = []
    w = driver.closed_loop(client, MIX_CLOSED, draws, 2.0, 4,
                           lambda: calls.append("open"),
                           lambda: calls.append("close"))
    assert calls == ["open", "close"]
    assert len(w.ticks) == 16 and w.seconds == 2.0
    assert all(t.banked == 4 for t in w.ticks)     # the batch stays full
    from importlib import util
    spec = util.spec_from_file_location(
        "m", Path(__file__).resolve().parents[1] / "metrics" / "out_tok_s.py")
    mod = util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert math.isclose(mod.read(_run(w)), 4 / DT)
    # every completion was answered by the same client at once
    assert len(client.tracked) == 8 + len(eng.done)


def test_closed_loop_opens_with_the_first_batch_caught_midway():
    """The first batch goes out longest first, each request with its
    stratum's share of the output already in its prompt; the rest queue
    fresh. Every seed opens with the same contexts."""
    mix = dict(MIX_CLOSED, clients=8, strata=4,
               prompt_tokens={"median": 8, "sigma": 0.5, "min": 2, "max": 20},
               output_tokens={"median": 9, "sigma": 0.5, "min": 2, "max": 30})
    shares = traffic.midway_shares(4)
    opened = []
    for seed in (3, 2**35 + 1):
        clock = Clock()
        client = driver.Client(FakeEngine(4, clock), Req, clock)
        firsts = list(_first(mix, seed, 8))
        driver.closed_loop(client, mix, traffic.stream(mix, seed, 50), 0.5,
                           4, lambda: None, lambda: None)
        sent = [client.tracked[u].req for u in range(8)]
        want = sorted((traffic.midway(d, shares[d.stratum])
                       for d in firsts[:4]), key=lambda d: -len(d.prompt))
        assert [len(r.prompt) for r in sent[:4]] == \
            [len(d.prompt) for d in want]
        assert [r.max_new_tokens for r in sent[:4]] == \
            [d.max_new_tokens for d in want]
        assert [r.max_new_tokens for r in sent[4:]] == \
            [d.max_new_tokens for d in firsts[4:]]
        opened.append([(len(r.prompt), r.max_new_tokens) for r in sent[:4]])
    assert opened[0] == opened[1]


def _first(mix, seed, n):
    it = traffic.stream(mix, seed, 50)
    return [next(it) for _ in range(n)]
