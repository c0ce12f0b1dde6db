"""Paper Table 11: runtime overhead of clipped softmax / gated attention
vs vanilla pre-training (measured per train step; the paper reports 1-8%%
on A100 — we report the CPU-tiny equivalent plus the kernel-level numbers)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import make_family
from repro.configs import apply_method
from repro.data import SyntheticLM, SyntheticLMConfig
from repro.optim import AdamWConfig
from repro.train import TrainTask, init_train_state, make_train_step

METHODS = [("vanilla", {}), ("clipped_softmax", {"alpha": 4.0}),
           ("gated_attention", {"pi_init": 0.5}),
           ("gated_attention_mlp", {"pi_init": 0.5, "gate_kind": "mlp"})]


def _time_steps(cfg, loss_kind, n=12):
    task = TrainTask(cfg=cfg, loss_kind=loss_kind,
                     optimizer=AdamWConfig(lr=1e-3))
    state = init_train_state(jax.random.PRNGKey(0), task)
    step = jax.jit(make_train_step(task), donate_argnums=(0,))
    data = SyntheticLM(SyntheticLMConfig(vocab_size=cfg.vocab_size,
                                         seq_len=64, batch_size=16))
    batch = jax.tree_util.tree_map(jnp.asarray, data.batch(0, loss_kind))
    state, m = step(state, batch)        # compile
    m["loss"].block_until_ready()
    t0 = time.perf_counter()
    for i in range(n):
        state, m = step(state, batch)
    m["loss"].block_until_ready()
    return (time.perf_counter() - t0) / n


def run(print_fn=print) -> None:
    cfg0, loss_kind = make_family("bert")
    print_fn("# Table 11 — runtime overhead per train step [BERT-family]")
    print_fn("method,us_per_step,overhead_vs_vanilla_pct")
    base = None
    for name, kw in METHODS:
        method = "gated_attention" if name.startswith("gated") else name
        cfg = apply_method(cfg0, method, **kw)
        s = _time_steps(cfg, loss_kind)
        base = s if base is None else base
        print_fn(f"{name},{s*1e6:.0f},{(s/base-1)*100:.1f}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
