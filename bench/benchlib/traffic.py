"""One general traffic generator, driven by a mix's parameter file.

A mix (``bench/traffic/<name>.json``) names its lengths and its clients;
nothing about a mix lives in code. Every seed sends the same requests in
another order: each block of ``strata`` requests holds one request per
equal-probability stratum (the prompt and output lengths at the stratum's
middle, paired the same way for every seed), and the seed permutes them
inside the block and draws the token ids. So runs with different seeds do
the same work, and the seed changes only which request comes when and
what its tokens are.

Keys of a mix:

- ``loop``: ``"closed"``: ``clients`` callers, each sending its next
  request when the previous one completes;
- ``prompt_tokens`` / ``output_tokens``: clipped lognormal
  ``{"median", "sigma", "min", "max"}``;
- ``max_total_tokens``: prompt + output never exceeds it (output shortened);
- ``strata``: block size of the stratified draw;
- ``warm_widths``: the live widths (blocks) whose tick programs set-up
  compiles or loads for every token bucket, before any traffic: those the
  window can reach;
- ``check``: how many served tokens (``min_tokens``) and requests at most
  (``max_requests``) the comparison with the reference takes.

The requests that fill the batch at the start are caught partway
(``midway``): a fixed share of each one's output, stratified over (0, 1]
and the same for every seed, is already generated and sent as part of its
prompt. The batch then opens at the closed loop's long-run mix of
contexts instead of at young ones.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist
from typing import Iterator, List

import numpy as np

_SEED_MASK = (1 << 32) - 1


@dataclasses.dataclass(frozen=True)
class Draw:
    """One request as the generator draws it."""
    index: int
    stratum: int
    prompt: np.ndarray          # (P,) int32 token ids
    max_new_tokens: int
    filler: np.ndarray          # (max_new_tokens,) ids standing for output
                                # already generated, when caught midway


def load_mix(path: Path) -> dict:
    mix = json.loads(Path(path).read_text())
    if mix.get("loop") != "closed":
        raise ValueError(f"{path}: loop must be 'closed'")
    return mix


def seed_words(seed: int) -> List[int]:
    """Split a whole-number seed of any size into 32-bit words."""
    if seed < 0:
        raise ValueError("seed must be >= 0")
    words = [seed & _SEED_MASK]
    seed >>= 32
    while seed:
        words.append(seed & _SEED_MASK)
        seed >>= 32
    return words


def lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    """Stratum midpoints of a clipped lognormal, as whole tokens."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def stream(mix: dict, seed: int, vocab: int) -> Iterator[Draw]:
    """Endless deterministic request stream of one mix and seed."""
    n = int(mix["strata"])
    prompts = lognormal_quantiles(mix["prompt_tokens"], n)
    # a fixed pairing of prompt and output strata: every seed sends the
    # same requests, the seed only orders them
    outputs = lognormal_quantiles(mix["output_tokens"], n)[
        np.random.default_rng(n).permutation(n)]
    cap = int(mix["max_total_tokens"])
    words = seed_words(seed)
    block = 0
    while True:
        rng = np.random.default_rng(words + [block])
        order = rng.permutation(n)
        for i, k in enumerate(order):
            plen = int(prompts[k])
            olen = max(1, min(int(outputs[k]), cap - plen))
            toks = rng.integers(0, vocab, size=plen + olen, dtype=np.int32)
            yield Draw(block * n + i, int(k), toks[:plen], olen, toks[plen:])
        block += 1


def midway_shares(strata: int) -> np.ndarray:
    """The share of its output still to come, per stratum: stratified
    uniform in (0, 1], paired with the strata the same way for every seed."""
    return np.random.default_rng(strata + 1).permutation(
        (np.arange(strata) + 0.5) / strata)


def midway(draw: Draw, share: float) -> Draw:
    """``draw`` caught with ``share`` of its output still to come: the rest
    is already generated and goes out with the prompt."""
    left = max(1, int(round(share * draw.max_new_tokens)))
    done = draw.max_new_tokens - left
    return dataclasses.replace(
        draw, prompt=np.concatenate([draw.prompt, draw.filler[:done]]),
        max_new_tokens=left, filler=draw.filler[done:])
