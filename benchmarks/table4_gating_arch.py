"""Paper Table 4/5 ablation: gating-function parameterizations
(Linear / MLP / All-heads-linear) + their parameter overhead."""
from __future__ import annotations

from benchmarks.common import bench_steps, HEADER, fmt_row, make_family, train_and_measure
from repro.configs import apply_method
from repro.core.gating import GateConfig, gate_param_count

KINDS = ["linear", "mlp", "all_heads_linear"]


def run(print_fn=print) -> None:
    cfg0, loss_kind = make_family("bert")
    print_fn("# Table 4 — gating architectures [BERT-family]")
    print_fn("gate,extra_params," + HEADER.split(",", 1)[1])
    for kind in KINDS:
        cfg = apply_method(cfg0, "gated_attention", pi_init=0.5,
                           gate_kind=kind)
        extra = gate_param_count(GateConfig(kind, n_hid=4), cfg.n_heads,
                                 cfg.head_dim, cfg.d_model) * cfg.n_layers
        r = train_and_measure(cfg, loss_kind, steps=bench_steps(0.5))
        print_fn(f"{kind},{extra}," + fmt_row("", r).split(",", 1)[1])


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
