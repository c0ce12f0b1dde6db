"""Kernel micro-benchmarks: Pallas (interpret) correctness-scale timings +
the XLA twins that actually run on CPU, plus int8-vs-float quality, plus
the paged-attention decode-tick scaling study (gather vs fused kernel
across block-table widths W, written to BENCH_paged_kernel.json). On TPU
the same harness times the compiled kernels (interpret=False)."""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.attention import AttentionConfig, chunked_attention, dense_attention, paged_attention
from repro.core.softmax import ClippedSoftmaxConfig
from repro.kernels import default_interpret, linear_w8a8, on_tpu, quantize_weights_int8

_BENCH_JSON = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                           "BENCH_paged_kernel.json")


def _time(fn, *args, n=5):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def bench_paged(print_fn=print, out_path: str = _BENCH_JSON) -> None:
    """Paged decode-tick scaling: one attention read per tick at batch B,
    each row holding ``live`` allocated blocks, as the block-table width W
    (the per-row logical capacity, max_len / block_size) grows.

    Three series per softmax variant:

      * ``gather_full``  — PR 2's status quo: the XLA gather materializes
        the full (B, W*block_size, Hkv, Dh) virtual sequence; cost grows
        linearly in W no matter how few tokens are live.
      * ``gather_live``  — the gather sliced to the allocated prefix via
        the scheduler's static ``live_width``; flat in W.
      * ``kernel_live``  — the fused Pallas kernel over the same prefix:
        in-place pool-block reads, no materialization. On CPU this column
        is INTERPRET-mode timing (absolute value meaningless, flatness in
        W is the claim); on TPU it is the compiled kernel.

    Results append-print as CSV and land in BENCH_paged_kernel.json so the
    perf trajectory is diffable across PRs."""
    B, HQ, HKV, DH, BS, LIVE = 4, 4, 2, 64, 16, 2
    WS = (8, 16, 32, 64)
    interpret = default_interpret()
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 4)
    pos = jnp.asarray([LIVE * BS - 1 - 3 * i for i in range(B)], jnp.int32)
    gate = jax.nn.sigmoid(jax.random.normal(ks[3], (B, 1, HQ)))
    variants = (("vanilla", ClippedSoftmaxConfig(), None),
                ("clipped", ClippedSoftmaxConfig(alpha=4.0), None),
                ("gated", ClippedSoftmaxConfig(alpha=4.0), gate))

    print_fn(f"# paged decode tick: B={B} Hq={HQ} Hkv={HKV} Dh={DH} "
             f"block_size={BS}, {LIVE} live blocks/row; kernel timings are "
             f"{'INTERPRET-mode (flatness in W is the claim)' if interpret else 'compiled'}")
    print_fn("variant,W,gather_full_us,gather_live_us,kernel_live_us")
    rows = []
    for name, sm, gp in variants:
        cfg = AttentionConfig(n_heads=HQ, n_kv_heads=HKV, d_head=DH,
                              softmax=sm, seq_len=max(WS) * BS)
        for w in WS:
            nb = B * LIVE + 2
            q = jax.random.normal(ks[0], (B, 1, HQ, DH))
            k_pool = jax.random.normal(ks[1], (nb, HKV, BS, DH))
            v_pool = jax.random.normal(ks[2], (nb, HKV, BS, DH))
            table = np.full((B, w), -1, np.int32)
            for i in range(B):
                table[i, :LIVE] = range(i * LIVE, (i + 1) * LIVE)
            table = jnp.asarray(table)

            def f(backend, lw):
                return jax.jit(lambda q, t: paged_attention(
                    q, k_pool, v_pool, t, cfg, q_offset=pos, gate_pi=gp,
                    backend=backend, live_width=lw))

            t_full = _time(f("gather", None), q, table)
            t_live = _time(f("gather", LIVE), q, table)
            t_kern = _time(f("kernel", LIVE), q, table)
            print_fn(f"{name},{w},{t_full*1e6:.0f},{t_live*1e6:.0f},"
                     f"{t_kern*1e6:.0f}")
            rows.append(dict(variant=name, W=w,
                             gather_full_us=round(t_full * 1e6, 1),
                             gather_live_us=round(t_live * 1e6, 1),
                             kernel_live_us=round(t_kern * 1e6, 1)))
    payload = {
        "meta": dict(B=B, Hq=HQ, Hkv=HKV, Dh=DH, block_size=BS,
                     live_blocks=LIVE, widths=list(WS),
                     backend=jax.default_backend(),
                     kernel_interpret_mode=interpret, on_tpu=on_tpu(),
                     note="gather_full scans the whole table width W; "
                          "gather_live/kernel_live visit only the allocated "
                          "prefix (scheduler live_width) and should be flat "
                          "in W. Interpret-mode kernel timings are only "
                          "meaningful for that flatness, not absolutely."),
        "rows": rows,
    }
    with open(out_path, "w") as fh:
        json.dump(payload, fh, indent=1)
    print_fn(f"# wrote {os.path.relpath(out_path)}")


def run(print_fn=print) -> None:
    print_fn("# Kernel micro-bench (CPU host; XLA paths timed, Pallas "
             "kernels are TPU-target and validated in tests)")
    print_fn("name,us_per_call,derived")
    key = jax.random.PRNGKey(0)
    B, T, H, HKV, D = 2, 512, 8, 4, 64
    q = jax.random.normal(key, (B, T, H, D), jnp.float32)
    k = jax.random.normal(key, (B, T, HKV, D), jnp.float32)
    v = jax.random.normal(key, (B, T, HKV, D), jnp.float32)

    for name, sm in (("attn_vanilla", ClippedSoftmaxConfig()),
                     ("attn_clipped", ClippedSoftmaxConfig(gamma=-0.03))):
        cfg = AttentionConfig(n_heads=H, n_kv_heads=HKV, d_head=D,
                              softmax=sm, chunk_size=128)
        f_dense = jax.jit(lambda q, k, v, c=cfg: dense_attention(q, k, v, c))
        f_chunk = jax.jit(lambda q, k, v, c=cfg: chunked_attention(q, k, v, c))
        td = _time(f_dense, q, k, v)
        tc = _time(f_chunk, q, k, v)
        flops = 4 * B * T * T * H * D
        print_fn(f"{name}_dense,{td*1e6:.0f},{flops/td/1e9:.1f}GFLOP/s")
        print_fn(f"{name}_chunked,{tc*1e6:.0f},{flops/tc/1e9:.1f}GFLOP/s")

    bench_paged(print_fn)

    # int8 path quality + time (XLA fallback timing on CPU)
    x = jax.random.normal(key, (256, 512))
    w = jax.random.normal(key, (512, 256)) * 0.05
    wq, ws = quantize_weights_int8(w)
    f = x @ w
    o = linear_w8a8(x, wq, ws)
    rel = float(jnp.mean(jnp.abs(o - f)) / jnp.mean(jnp.abs(f)))
    tf = _time(jax.jit(lambda a, b: a @ b), x, w)
    print_fn(f"matmul_f32,{tf*1e6:.0f},w8a8_rel_err={rel:.4f}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
