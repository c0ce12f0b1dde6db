"""Benchmark entrypoint: one section per paper table/figure + kernel
micro-bench + the roofline aggregation.

    PYTHONPATH=src python -m benchmarks.run            # full (~REPRO_BENCH_STEPS)
    REPRO_BENCH_STEPS=20 PYTHONPATH=src python -m benchmarks.run   # smoke

Sections print CSV blocks (``name,us_per_call,derived``-style columns per
table)."""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks import (  # noqa: E402
    fig6_alpha_vs_seqlen,
    fig7_bias_init,
    kernel_bench,
    roofline_report,
    table1_clipped_softmax,
    table2_main,
    table4_gating_arch,
    table10_bitwidths,
    table11_overhead,
)
from repro.compile_cache import enable_compile_cache  # noqa: E402

SECTIONS = [
    ("table2_main", table2_main.run),
    ("table1_clipped_softmax", table1_clipped_softmax.run),
    ("fig6_alpha_vs_seqlen", fig6_alpha_vs_seqlen.run),
    ("fig7_bias_init", fig7_bias_init.run),
    ("table4_gating_arch", table4_gating_arch.run),
    ("table10_bitwidths", table10_bitwidths.run),
    ("table11_overhead", table11_overhead.run),
    ("kernel_bench", kernel_bench.run),
    ("roofline_report", roofline_report.run),
]


def main() -> None:
    enable_compile_cache()
    only = set(sys.argv[1:])
    failed = []
    t_all = time.time()
    for name, fn in SECTIONS:
        if only and name not in only:
            continue
        t0 = time.time()
        print(f"\n===== {name} =====", flush=True)
        try:
            fn()
        except Exception as e:  # noqa: BLE001 — report, run the rest, fail
            print(f"SECTION FAILED: {name}: {e!r}", flush=True)
            failed.append(name)
        print(f"# section {name} took {time.time()-t0:.1f}s", flush=True)
    print(f"\n# total {time.time()-t_all:.1f}s")
    if failed:
        sys.exit(f"{len(failed)} section(s) failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
