"""Paper Table 2 (main results): vanilla vs clipped softmax vs gated
attention on the BERT-family (MLM) and OPT-family (CLM) protocols.
Reports FP ppl / max inf-norm / kurtosis / W8A8 ppl per method."""
from __future__ import annotations

from benchmarks.common import HEADER, fmt_row, make_family, train_and_measure
from repro.configs import apply_method

METHODS = [
    ("vanilla", {}),
    ("clipped_softmax", {"alpha": 4.0}),
    ("gated_attention", {"pi_init": 0.5}),
]


def run(print_fn=print) -> None:
    for family in ("bert", "opt"):
        cfg0, loss_kind = make_family(family)
        print_fn(f"# Table 2 — main results [{family}-family {loss_kind}]")
        print_fn(HEADER)
        for method, kw in METHODS:
            cfg = apply_method(cfg0, method, **kw)
            r = train_and_measure(cfg, loss_kind)
            print_fn(fmt_row(f"{family}/{method}", r))


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
