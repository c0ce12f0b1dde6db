"""Paper Table 10: low-bit PTQ sweep (W8A8 / W6A8 / W4A8 / W6A6) with
min-max vs MSE weight-range estimation, on a clipped-softmax-trained model
vs a vanilla one."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import bench_steps, make_family, train_and_measure
from repro.configs import apply_method
from repro.models import model_apply
from repro.quant import QConfig, QuantContext, calibrate, evaluate_perplexity
from repro.train.losses import loss_for

SETTINGS = [
    ("W8A8/minmax", QConfig(weight_bits=8, act_bits=8)),
    ("W6A8/mse", QConfig(weight_bits=6, act_bits=8, weight_estimator="mse")),
    ("W4A8/mse", QConfig(weight_bits=4, act_bits=8, weight_estimator="mse")),
    ("W6A6/mse", QConfig(weight_bits=6, act_bits=6, weight_estimator="mse")),
]


def run(print_fn=print) -> None:
    cfg0, loss_kind = make_family("bert")
    print_fn("# Table 10 — low-bit PTQ sweep [BERT-family]")
    print_fn("method,setting,fp_ppl,q_ppl")
    for method, kw in (("vanilla", {}), ("clipped_softmax", {"alpha": 4.0})):
        cfg = apply_method(cfg0, method, **kw)
        r = train_and_measure(cfg, loss_kind, steps=bench_steps(0.75))
        params, data = r["params"], r["data"]

        def apply_fn(p, batch, ctx):
            logits, _ = model_apply(p, cfg, batch, ctx=ctx)
            return logits

        def loss_fn(p, batch, ctx):
            ctx = ctx if ctx is not None else QuantContext(None)
            logits, _ = model_apply(p, cfg, batch, ctx=ctx)
            return loss_for(loss_kind)(logits, jnp.asarray(batch["labels"]))

        for name, qc in SETTINGS:
            cal = [jax.tree_util.tree_map(jnp.asarray,
                                          data.batch(5_000_000 + i, loss_kind))
                   for i in range(8)]
            ctx = calibrate(apply_fn, params, cal, qc, 8)
            ev = [jax.tree_util.tree_map(jnp.asarray,
                                         data.batch(10_000_000 + i, loss_kind))
                  for i in range(4)]
            q = evaluate_perplexity(loss_fn, params, ev, ctx, 4)
            print_fn(f"{method},{name},{r['fp_ppl']:.3f},{q:.3f}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
