"""Pallas TPU RG-LRU linear-recurrence kernel (recurrentgemma's mixer).

h_t = a_t * h_{t-1} + b_t, elementwise-diagonal — purely memory-bound
(2 loads + 1 store per element, zero matmuls). The XLA path uses
``associative_scan`` (log-depth, but 3x the HBM traffic from tree
intermediates); this kernel streams time sequentially while the recurrent
state lives in VMEM, hitting the 1-read-1-write minimum. Griffin's GPU
implementation makes the same trade (their "linear scan" kernel); this is
the TPU equivalent.

Grid (B, D/bd): each program owns a (T, bd) strip; time runs in a
fori_loop over VMEM-resident blocks. The feature dim is blocked at 512
lanes so (a, b, h) strips fit VMEM for T up to ~8k per call; longer
sequences chunk at the ops.py level, carrying h across calls.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import default_interpret


def _kernel(a_ref, b_ref, h0_ref, h_ref, hlast_ref, *, t_len):
    h = h0_ref[0]                                  # (bd,)

    def body(i, h):
        h_new = a_ref[0, i, :] * h + b_ref[0, i, :]
        h_ref[0, i, :] = h_new.astype(h_ref.dtype)
        return h_new

    h = jax.lax.fori_loop(0, t_len, body, h)
    hlast_ref[0] = h


def rglru_pallas(a: jax.Array, b: jax.Array, h0=None, *,
                 block_d: int = 512, interpret: Optional[bool] = None):
    """a, b: (B, T, D) f32; h0: (B, D) or None.
    Returns (h (B,T,D) f32, h_last (B,D))."""
    if interpret is None:
        interpret = default_interpret()
    bsz, t, d = a.shape
    if h0 is None:
        h0 = jnp.zeros((bsz, d), jnp.float32)
    block_d = min(block_d, d)
    pad_d = (-d) % block_d
    if pad_d:
        a = jnp.pad(a, ((0, 0), (0, 0), (0, pad_d)))
        b = jnp.pad(b, ((0, 0), (0, 0), (0, pad_d)))
        h0 = jnp.pad(h0, ((0, 0), (0, pad_d)))
    dp = d + pad_d
    grid = (bsz, dp // block_d)
    h, hlast = pl.pallas_call(
        functools.partial(_kernel, t_len=t),
        grid=grid,
        in_specs=[pl.BlockSpec((1, t, block_d), lambda i, j: (i, 0, j)),
                  pl.BlockSpec((1, t, block_d), lambda i, j: (i, 0, j)),
                  pl.BlockSpec((1, block_d), lambda i, j: (i, j))],
        out_specs=[pl.BlockSpec((1, t, block_d), lambda i, j: (i, 0, j)),
                   pl.BlockSpec((1, block_d), lambda i, j: (i, j))],
        out_shape=[jax.ShapeDtypeStruct((bsz, t, dp), jnp.float32),
                   jax.ShapeDtypeStruct((bsz, dp), jnp.float32)],
        interpret=interpret,
    )(a.astype(jnp.float32), b.astype(jnp.float32), h0.astype(jnp.float32))
    return h[..., :d], hlast[..., :d]
