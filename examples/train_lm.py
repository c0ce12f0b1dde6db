"""End-to-end training driver: pre-train an LM with the paper's method,
with checkpointing, outlier telemetry and final PTQ — the paper's whole
experimental pipeline as one script.

    PYTHONPATH=src python examples/train_lm.py --method clipped_softmax \
        --steps 300 --arch opt-tiny
    PYTHONPATH=src python examples/train_lm.py --arch granite-moe-1b-a400m \
        --smoke --steps 50           # any pool arch (reduced config)
"""
import argparse
import dataclasses
import os

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs import apply_method, get_arch
from repro.configs.paper_models import opt_tiny
from repro.data import SyntheticLM, SyntheticLMConfig
from repro.models import model_apply
from repro.optim import AdamWConfig, linear_warmup_linear_decay
from repro.quant import QConfig, QuantContext, calibrate, evaluate_perplexity
from repro.train import LoopConfig, TrainTask, run_training
from repro.train.losses import loss_for


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="opt-tiny")
    ap.add_argument("--method", default="clipped_softmax",
                    choices=["vanilla", "clipped_softmax", "gated_attention"])
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--lr", type=float, default=2e-3)
    ap.add_argument("--smoke", action="store_true",
                    help="use the arch's reduced smoke config")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    if args.arch == "opt-tiny":
        cfg = opt_tiny(vocab=512, seq_len=args.seq_len)
    else:
        spec = get_arch(args.arch)
        cfg = spec.smoke() if args.smoke else spec.full()
        cfg = dataclasses.replace(cfg, train_seq_len=args.seq_len)
    cfg = apply_method(cfg, args.method, alpha=4.0, pi_init=0.5)
    loss_kind = "clm" if cfg.causal else "frames"

    task = TrainTask(
        cfg=cfg, loss_kind=loss_kind,
        optimizer=AdamWConfig(lr=args.lr),
        schedule=linear_warmup_linear_decay(args.steps // 10, args.steps))
    data = SyntheticLM(SyntheticLMConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq_len,
        batch_size=args.batch_size))

    print(f"== training {cfg.name} [{args.method}] for {args.steps} steps ==")
    out = run_training(task, data, LoopConfig(
        total_steps=args.steps, eval_every=max(args.steps // 4, 1),
        eval_batches=4, log_every=max(args.steps // 10, 1),
        ckpt_every=args.steps // 2 if args.ckpt_dir else 0,
        ckpt_dir=args.ckpt_dir), batch_kind=loss_kind)
    print(f"median step: {out['median_step_s']*1e3:.0f} ms, "
          f"stragglers: {out['stragglers']}")

    # ---- the paper's PTQ epilogue ----
    params = out["state"].params

    def apply_fn(p, b, ctx):
        return model_apply(p, cfg, b, ctx=ctx)[0]

    def loss_fn(p, b, ctx):
        ctx = ctx if ctx is not None else QuantContext(None)
        logits, _ = model_apply(p, cfg, b, ctx=ctx)
        return loss_for(loss_kind)(logits, jnp.asarray(b["labels"]))

    cal = [jax.tree_util.tree_map(jnp.asarray, data.batch(10_000 + i, loss_kind))
           for i in range(8)]
    ctx = calibrate(apply_fn, params, cal, QConfig(), 8)
    fp = evaluate_perplexity(loss_fn, params, cal, None, 4)
    q8 = evaluate_perplexity(loss_fn, params, cal, ctx, 4)
    print(f"FP ppl {fp:.3f} -> W8A8 ppl {q8:.3f} "
          f"(gap {100 * (q8 / fp - 1):.2f}%)")


if __name__ == "__main__":
    main()
