"""Shared harness for the paper-table benchmarks.

Each benchmark runs the paper's *protocol* at CPU scale: pre-train a reduced
same-family model on synthetic Markov data, record FP perplexity + outlier
metrics (max inf-norm, avg kurtosis over attention-layer outputs), then
apply the paper's PTQ recipe (symmetric-weight/asymmetric-activation,
static ranges) and record quantized perplexity.

Step counts scale with REPRO_BENCH_STEPS (default 200; CI smoke uses 20).
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np


from repro.configs import apply_method
from repro.configs.paper_models import bert_tiny, opt_tiny
from repro.data import SyntheticLM, SyntheticLMConfig
from repro.models import model_apply
from repro.optim import AdamWConfig, linear_warmup_linear_decay
from repro.quant import QConfig, QuantContext, calibrate, evaluate_perplexity
from repro.train import LoopConfig, TrainTask, evaluate, run_training
from repro.train.losses import loss_for

BENCH_STEPS = int(os.environ.get("REPRO_BENCH_STEPS", "200"))
VOCAB = 512


def bench_steps(scale: float = 1.0) -> int:
    return max(int(BENCH_STEPS * scale), 5)


def make_family(family: str):
    """'bert' (MLM, post-LN encoder, trained at 128 tokens) or 'opt' (CLM,
    pre-LN decoder, trained at 256)."""
    if family == "bert":
        return bert_tiny(vocab=VOCAB), "mlm"
    return opt_tiny(vocab=VOCAB), "clm"


def train_and_measure(
    cfg,
    loss_kind: str,
    steps: Optional[int] = None,
    lr: float = 2e-3,
    seed: int = 0,
    batch_size: int = 16,
    qconfig: Optional[QConfig] = None,
) -> Dict[str, float]:
    """Paper protocol: pre-train -> (FP ppl, inf-norm, kurtosis, W8A8 ppl)."""
    steps = steps or BENCH_STEPS
    task = TrainTask(cfg=cfg, loss_kind=loss_kind,
                     optimizer=AdamWConfig(lr=lr),
                     schedule=linear_warmup_linear_decay(steps // 10, steps))
    data = SyntheticLM(SyntheticLMConfig(
        vocab_size=cfg.vocab_size, seq_len=cfg.train_seq_len,
        batch_size=batch_size, seed=seed))
    t0 = time.perf_counter()
    out = run_training(task, data, LoopConfig(
        total_steps=steps, eval_every=0, log_every=0), batch_kind=loss_kind)
    train_s = time.perf_counter() - t0
    params = out["state"].params
    ppl, ostats = evaluate(task, params, data, n_batches=4, batch_kind=loss_kind)

    res = {
        "fp_ppl": ppl,
        "max_inf_norm": ostats["max_inf_norm"],
        "avg_kurtosis": ostats["avg_kurtosis"],
        "train_s": train_s,
        "s_per_step": train_s / steps,
    }

    # ---- PTQ (paper Sec. 5 'Quantization setup') ----
    qc = qconfig or QConfig(act_estimator="running_minmax")

    def apply_fn(p, batch, ctx):
        logits, _ = model_apply(p, cfg, batch, ctx=ctx)
        return logits

    def loss_fn(p, batch, ctx):
        ctx = ctx if ctx is not None else QuantContext(None)
        logits, _ = model_apply(p, cfg, batch, ctx=ctx)
        return loss_for(loss_kind)(logits, jnp.asarray(batch["labels"]))

    q_ppls = []
    for cal_seed in range(2):
        cal = [jax.tree_util.tree_map(
            jnp.asarray, data.batch(5_000_000 + 100 * cal_seed + i, loss_kind))
            for i in range(8)]
        ctx = calibrate(apply_fn, params, cal, qc, num_batches=8)
        ev = [jax.tree_util.tree_map(
            jnp.asarray, data.batch(10_000_000 + i, loss_kind))
            for i in range(4)]
        q_loss = jax.jit(lambda p, b: loss_fn(p, b, ctx))
        q_ppls.append(evaluate_perplexity(
            lambda p, b, _ctx: q_loss(p, b), params, ev, ctx, 4))
    res["w8a8_ppl"] = float(np.mean(q_ppls))
    res["w8a8_ppl_std"] = float(np.std(q_ppls))
    res["params"] = params
    res["task"] = task
    res["data"] = data
    return res


def fmt_row(name: str, r: Dict[str, float]) -> str:
    return (f"{name},{r['fp_ppl']:.3f},{r['max_inf_norm']:.2f},"
            f"{r['avg_kurtosis']:.1f},{r['w8a8_ppl']:.3f},"
            f"{r['s_per_step']*1e6:.0f}")


HEADER = "name,fp_ppl,max_inf_norm,avg_kurtosis,w8a8_ppl,us_per_step"
