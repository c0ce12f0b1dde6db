"""The comparison that decides ``correct``.

Served tokens are greedy, so each one should be the reference's best token
at its position, up to rounding. For a sample of finished requests (drawn
from the seed, with the longest always in it), the reference runs once
over each prompt with its served tokens, and each served token's gap is
how far the reference's logit for it lies below the reference's best
logit there. ``max_gap`` is the widest gap over the sample, and
``verdict`` holds it to the configuration's limit. The control is the
reference computed in a lower precision, read at the same positions: its
gap is that of the token it puts first, and the same ``verdict`` judges it.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from benchlib.traffic import seed_words


def sample(finished: Sequence, seed: int, min_tokens: int,
           max_requests: int) -> List:
    """The longest finished request (prompt + output), then others in seed
    order until ``min_tokens`` served tokens or ``max_requests``."""
    if not finished:
        return []
    size = [len(r.prompt) + len(r.output) for r in finished]
    first = int(np.argmax(size))
    rng = np.random.default_rng(seed_words(seed) + [7])
    rest = [i for i in rng.permutation(len(finished)) if i != first]
    out, n = [finished[first]], len(finished[first].output)
    for i in rest:
        if n >= min_tokens or len(out) >= max_requests:
            break
        out.append(finished[i])
        n += len(finished[i].output)
    return out


def verdict(gaps: np.ndarray, limit: float) -> Dict:
    """The widest gap and whether it keeps to ``limit``; no gap at all is
    not correct."""
    widest = float(gaps.max()) if gaps.size else float("inf")
    return {"max_gap": widest, "correct": bool(gaps.size and widest <= limit)}


def _rows(req, max_len: int):
    """The sequence fed, padded to the smallest power of two that holds it
    (at most ``max_len``), the positions that produced the served tokens
    (padded to a power of two by repeating the last) and the tokens. Powers
    of two let the reference compile a few shapes, not one per length; the
    causal reference reads no padding at the positions compared."""
    p, o = np.asarray(req.prompt, np.int32), np.asarray(req.output, np.int32)
    fed = np.concatenate([p, o[:-1]])
    seq = np.zeros(min(max_len, 1 << (len(fed) - 1).bit_length()), np.int32)
    seq[:len(fed)] = fed
    n = 1 << (len(o) - 1).bit_length()
    at = np.full(n, len(p) - 2 + len(o), np.int32)
    at[:len(o)] = np.arange(len(p) - 1, len(p) - 1 + len(o), dtype=np.int32)
    return seq, at, o


def gaps(requests: Sequence, ref: Callable, params, max_len: int,
         control: Optional[Callable] = None) -> Dict[str, np.ndarray]:
    """Per served token: ``served`` gap of the served token, and with a
    control, ``control`` gap of the control's first choice."""
    served, ctrl = [], []
    for r in requests:
        seq, at, out = _rows(r, max_len)
        k = len(out)
        lg = np.asarray(ref(params, seq, at)[:k], np.float64)
        best = lg.max(axis=-1)
        served.append(best - lg[np.arange(k), out])
        if control is not None:
            c = np.asarray(control(params, seq, at)[:k]).argmax(axis=-1)
            ctrl.append(best - lg[np.arange(k), c])
    res = {"served": np.concatenate(served) if served else np.zeros(0)}
    if control is not None:
        res["control"] = np.concatenate(ctrl) if ctrl else np.zeros(0)
    return res
