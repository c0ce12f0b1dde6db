"""Pallas fused fake-quant (Eq. 1): one pass over the tensor applying
quant-dequant with static (s, z). In a PTQ serving graph this op brackets
every matmul; fusing it keeps the activation tensor's HBM round-trips at
1 read + 1 write (it is purely memory-bound: arithmetic intensity ~5
flops/byte-pair, far below the v5e ridge point, so bandwidth IS the
roofline and the win is not re-materializing intermediates)."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ops import default_interpret


def _kernel(x_ref, o_ref, *, s, z, n_levels):
    x = x_ref[...].astype(jnp.float32)
    q = jnp.clip(jnp.round(x / s + z), 0.0, n_levels - 1.0)
    o_ref[...] = (s * (q - z)).astype(o_ref.dtype)


def fake_quant_pallas(x: jax.Array, s: float, z: float, bits: int = 8,
                      block: int = 1024,
                      interpret: Optional[bool] = None) -> jax.Array:
    """Per-tensor fake-quant; static python-float (s, z) baked into the
    kernel (the PTQ context provides them after calibration)."""
    if interpret is None:
        interpret = default_interpret()
    orig_shape = x.shape
    flat = x.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % block
    if pad:
        flat = jnp.pad(flat, (0, pad))
    out = pl.pallas_call(
        functools.partial(_kernel, s=float(s), z=float(z), n_levels=2 ** bits),
        grid=(flat.shape[0] // block,),
        in_specs=[pl.BlockSpec((block,), lambda i: (i,))],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct(flat.shape, x.dtype),
        interpret=interpret,
    )(flat)
    return out[:n].reshape(orig_shape)
