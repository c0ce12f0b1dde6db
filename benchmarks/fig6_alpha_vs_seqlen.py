"""Paper Figure 6: gamma = -alpha/T parameterization across sequence
lengths — alpha in [2, 4] should hold up across T (BERT-6L protocol,
reduced)."""
from __future__ import annotations

from benchmarks.common import bench_steps, HEADER, fmt_row, train_and_measure
from repro.configs import apply_method
from repro.configs.paper_models import bert_tiny

ALPHAS = [0.5, 2.0, 4.0, 8.0]
SEQ_LENS = [32, 64, 128]


def run(print_fn=print) -> None:
    print_fn("# Fig 6 — gamma = -alpha/T vs sequence length [BERT-family]")
    print_fn("seq_len,alpha," + HEADER.split(",", 1)[1])
    for t in SEQ_LENS:
        for alpha in ALPHAS:
            cfg = apply_method(bert_tiny(vocab=512, seq_len=t),
                               "clipped_softmax", alpha=alpha)
            r = train_and_measure(cfg, "mlm", steps=bench_steps(0.4))
            print_fn(f"{t},{alpha}," + fmt_row("", r).split(",", 1)[1])


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
