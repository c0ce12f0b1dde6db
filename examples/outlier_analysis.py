"""Reproduce the paper's Section 3 outlier analysis on a trained model:
outlier counts per hidden dimension / token position (Fig. 1), attention
concentration on low-information tokens, and the vanilla-vs-clipped
contrast.

    PYTHONPATH=src python examples/outlier_analysis.py --steps 400
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import apply_method
from repro.configs.paper_models import bert_tiny
from repro.core import outlier_counts_by_dim, outlier_counts_by_token
from repro.data import SyntheticLM, SyntheticLMConfig
from repro.models import model_apply
from repro.optim import AdamWConfig
from repro.train import LoopConfig, TrainTask, run_training


def analyze(params, cfg, batch, label):
    _, aux = model_apply(params, cfg, batch, collect_acts=True)
    acts = aux["attn_outputs"]
    last = acts[-1]                                     # (B, T, D)
    by_dim = np.asarray(outlier_counts_by_dim(last))
    by_tok = np.asarray(outlier_counts_by_token(last))
    inf = float(jnp.max(jnp.abs(last)))
    print(f"\n[{label}] last-layer attention output:")
    print(f"  max |x|        : {inf:.2f}")
    print(f"  outliers (6s)  : {by_dim.sum()}")
    if by_dim.sum():
        top = np.argsort(by_dim)[-3:][::-1]
        print(f"  top hidden dims: {[(int(d), int(by_dim[d])) for d in top]}")
        ttop = np.argsort(by_tok)[-3:][::-1]
        print(f"  top token pos  : {[(int(t), int(by_tok[t])) for t in ttop]}")


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=400)
    args = ap.parse_args()

    data = SyntheticLM(SyntheticLMConfig(vocab_size=512, seq_len=64,
                                         batch_size=16))
    batch = jax.tree_util.tree_map(jnp.asarray, data.batch(12345, "mlm"))

    for method in ("vanilla", "clipped_softmax"):
        cfg = apply_method(bert_tiny(vocab=512, seq_len=64), method, alpha=4.0)
        task = TrainTask(cfg=cfg, loss_kind="mlm",
                         optimizer=AdamWConfig(lr=2e-3))
        out = run_training(task, data, LoopConfig(
            total_steps=args.steps, eval_every=0, log_every=args.steps // 4),
            batch_kind="mlm")
        analyze(out["state"].params, cfg, batch, method)


if __name__ == "__main__":
    main()
