"""Paper Figure 7: gated-attention bias init (pi_init) sweep — very low
pi_init hurts FP quality, very high behaves like vanilla (outliers return);
the useful band is wide (robustness claim)."""
from __future__ import annotations

from benchmarks.common import bench_steps, HEADER, fmt_row, make_family, train_and_measure
from repro.configs import apply_method

PI_INITS = [0.05, 0.25, 0.5, 0.9, 0.99]


def run(print_fn=print) -> None:
    cfg0, loss_kind = make_family("bert")
    print_fn("# Fig 7 — gated attention pi_init sweep [BERT-family]")
    print_fn("pi_init," + HEADER.split(",", 1)[1])
    for pi in PI_INITS:
        cfg = apply_method(cfg0, "gated_attention", pi_init=pi)
        r = train_and_measure(cfg, loss_kind, steps=bench_steps(0.5))
        print_fn(f"{pi}," + fmt_row("", r).split(",", 1)[1])


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
