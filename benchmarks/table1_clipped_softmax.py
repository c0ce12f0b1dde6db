"""Paper Table 1: the impact of clipped-softmax stretch factors (gamma,
zeta) on FP ppl, outlier metrics and W8A8 ppl — BERT-family MLM protocol.

Paper finding to reproduce: gamma < 0 (exact zeros) does the work; zeta > 1
behaves like vanilla; combining adds nothing.
"""
from __future__ import annotations

from benchmarks.common import bench_steps, HEADER, fmt_row, make_family, train_and_measure
from repro.configs import apply_method

GRID = [
    ("vanilla(g=0,z=1)", 0.0, 1.0),
    ("g=0,z=1.03", 0.0, 1.03),
    ("g=-0.003,z=1", -0.003, 1.0),
    ("g=-0.03,z=1", -0.03, 1.0),
    ("g=-0.03,z=1.03", -0.03, 1.03),
]


def run(print_fn=print) -> None:
    cfg0, loss_kind = make_family("bert")
    print_fn("# Table 1 — clipped softmax (gamma, zeta) [BERT-family MLM]")
    print_fn(HEADER)
    for name, gamma, zeta in GRID:
        if gamma == 0.0 and zeta == 1.0:
            cfg = apply_method(cfg0, "vanilla")
        else:
            cfg = apply_method(cfg0, "clipped_softmax", gamma=gamma, zeta=zeta)
        r = train_and_measure(cfg, loss_kind, steps=bench_steps(0.5))
        print_fn(fmt_row(name, r))


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
