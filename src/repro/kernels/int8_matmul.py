"""Pallas TPU W8A8 matmul — the integer-compute payoff the paper's
architecture changes unlock.

On TPU the MXU natively consumes int8 operands with int32 accumulation
(~2x bf16 throughput on v5e). This kernel implements the paper's W8A8
scheme end-to-end:

  * activations: per-tensor *asymmetric* uint8 (scale s_x, zero-point z_x),
    quantized on the fly in the prologue of each block — legal BECAUSE the
    paper's clipped-softmax/gated-attention models have no outliers, so a
    static per-tensor range works (Table 2);
  * weights: per-tensor symmetric int8 (pre-quantized, scale s_w);
  * integer matmul with the zero-point folded out exactly: the uint8
    codes are shifted into int8, x_c = x_q - 128, and
        (x_q - z_x) @ w_q = x_c @ w_q + (128 - z_x) * colsum(w_q).
    Both operands enter ``dot_general`` as int8 with
    ``preferred_element_type=int32`` (the MXU's int8 path; widening them
    to int32 first is refused by the TPU compiler) and accumulate in an
    int32 VMEM scratch; the column sums of the weight tiles already in
    VMEM accumulate next to it, so the correction reads no extra HBM.
    The integer offset 128 - z_x is scalar-prefetched into SMEM;
  * epilogue: dequantize by s_x * s_w.

Grid (M/bm, N/bn, K/bk), K sequential with the accumulator scratch.
256x256x256 int8 blocks = 2 x 64 KB operands + 256 KB int32 accumulator
+ 256 KB f32 output block, comfortably double-buffered in VMEM.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import default_interpret


def _kernel(off_ref, xq_ref, wq_ref, o_ref, acc_scr, cs_scr, *, n_k):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        cs_scr[...] = jnp.zeros_like(cs_scr)

    # int8 x int8 -> int32 on the MXU
    w = wq_ref[...]
    acc_scr[...] += jax.lax.dot_general(
        xq_ref[...], w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    cs_scr[...] += jnp.sum(w.astype(jnp.int32), axis=0, keepdims=True)

    @pl.when(k_idx == n_k - 1)
    def _():
        o_ref[...] = (acc_scr[...] + off_ref[0] * cs_scr[...]
                      ).astype(o_ref.dtype)


def quantize_weights_int8(w: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-tensor int8 weight quantization (paper §C.4)."""
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)))
    scale = jnp.maximum(amax / 127.0, 1e-8)
    wq = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -127, 127
                  ).astype(jnp.int8)
    return wq, scale


def int8_matmul(
    x: jax.Array,            # (M, K) float
    w_q: jax.Array,          # (K, N) int8 (symmetric)
    w_scale: jax.Array,      # scalar f32
    *,
    x_scale: float = None,   # static PTQ-calibrated activation scale
    x_zero: float = None,    # static activation zero-point (uint8 domain)
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 256,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Full W8A8 matmul: per-tensor asymmetric activation quant + integer
    kernel + dequant. Returns f32 (M, N).

    When ``x_scale``/``x_zero`` are given (PTQ-calibrated static ranges,
    e.g. from QuantContext.act_qparams) the dynamic min/max pass over x is
    skipped — the production serving configuration. Without them the range
    is derived from this batch (dynamic quantization). ``interpret``
    defaults to ``kernels.ops.default_interpret()``."""
    if interpret is None:
        interpret = default_interpret()
    m, kdim = x.shape
    n = w_q.shape[1]
    # activation quantization (asymmetric uint8, zero-point folded out)
    block_m = min(block_m, m)
    block_n = min(block_n, n)
    block_k = min(block_k, kdim)
    x32 = x.astype(jnp.float32)
    if x_scale is None:
        x_min = jnp.minimum(jnp.min(x32), 0.0)
        x_max = jnp.maximum(jnp.max(x32), 0.0)
        s_x = jnp.maximum((x_max - x_min) / 255.0, 1e-8)
        z_x = jnp.clip(jnp.round(-x_min / s_x), 0, 255)
    else:
        s_x = jnp.float32(x_scale)
        z_x = jnp.float32(0.0 if x_zero is None else x_zero)
    # uint8 codes shifted into int8 (exact); the kernel adds back
    # (128 - z) * colsum(w_q), so no code is clipped beyond [0, 255]
    xq_c = (jnp.clip(jnp.round(x32 / s_x) + z_x, 0, 255) - 128
            ).astype(jnp.int8)
    off = jnp.round(128.0 - z_x).astype(jnp.int32).reshape(1)

    # zero-pad to block multiples: int blocks pad with garbage otherwise;
    # zero weight rows/columns add nothing to the dot or the column sums
    pad_m = (-m) % block_m
    pad_k = (-kdim) % block_k
    pad_n = (-n) % block_n
    if pad_m or pad_k:
        xq_c = jnp.pad(xq_c, ((0, pad_m), (0, pad_k)))
    if pad_k or pad_n:
        w_q = jnp.pad(w_q, ((0, pad_k), (0, pad_n)))

    grid = (pl.cdiv(m, block_m), pl.cdiv(n, block_n), pl.cdiv(kdim, block_k))
    out = pl.pallas_call(
        functools.partial(_kernel, n_k=grid[2]),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_m, block_k), lambda i, j, kk, o: (i, kk)),
                pl.BlockSpec((block_k, block_n), lambda i, j, kk, o: (kk, j))],
            out_specs=pl.BlockSpec((block_m, block_n),
                                   lambda i, j, kk, o: (i, j)),
            scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32),
                            pltpu.VMEM((1, block_n), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((m + pad_m, n + pad_n), jnp.float32),
        interpret=interpret,
        name="int8_matmul",
    )(off, xq_c, w_q)
    return out[:m, :n] * (s_x * w_scale)
