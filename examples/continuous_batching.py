"""Continuous-batching serving demo: requests of different lengths stream
through a fixed slot pool; finished slots refill from the queue without
draining the batch. Every active slot decodes on every tick at its own
position (per-row cache scatter) — no lockstep cohorts — and requests stop
early at EOS.

    PYTHONPATH=src python examples/continuous_batching.py
"""
import time

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import apply_method
from repro.configs.paper_models import opt_tiny
from repro.models import model_init
from repro.serving import ContinuousBatcher, Request

EOS_ID = 5          # synthetic EOS: some requests will emit it mid-stream


def main() -> None:
    enable_compile_cache()
    cfg = apply_method(opt_tiny(vocab=256, seq_len=64), "clipped_softmax",
                       alpha=4.0)
    params = model_init(jax.random.PRNGKey(0), cfg)
    batcher = ContinuousBatcher(params, cfg, batch_size=4, max_len=64,
                                eos_id=EOS_ID)

    rng = np.random.default_rng(0)
    n_req = 10
    for i in range(n_req):
        batcher.submit(Request(
            uid=i,
            prompt=rng.integers(4, 256, size=int(rng.integers(4, 12))).astype(np.int32),
            max_new_tokens=int(rng.integers(4, 10))))

    t0 = time.perf_counter()
    ticks = 0
    while batcher.queue or any(s.req for s in batcher.slots):
        active = batcher.step()
        ticks += 1
        if ticks % 5 == 0:
            print(f"tick {ticks:3d}: {active} active slots, "
                  f"{len(batcher.queue)} queued, {len(batcher.done)} done")
    dt = time.perf_counter() - t0
    total_tokens = sum(len(r.output) for r in batcher.done)
    print(f"\nserved {len(batcher.done)}/{n_req} requests, "
          f"{total_tokens} tokens in {dt:.1f}s over {ticks} ticks "
          f"({total_tokens/dt:.1f} tok/s)")
    for r in sorted(batcher.done, key=lambda r: r.uid)[:3]:
        stop = "EOS" if len(r.output) and r.output[-1] == EOS_ID else "budget"
        print(f"  req {r.uid}: prompt[{len(r.prompt)}] -> {r.output.tolist()} ({stop})")


if __name__ == "__main__":
    main()
