"""Jit'd public wrappers around the Pallas kernels, with model-layout
adapters (the kernels use flattened (B*H, T, D) layouts), and the one rule
that decides interpret mode for every kernel entry point.

``default_interpret()``: kernels run compiled on TPU and in the Pallas
interpreter everywhere else. Each entry point takes ``interpret=None`` and
resolves it here; only tests pass it explicitly (``interpret=True`` for a
CPU sweep, ``interpret=False`` for a compile for a described TPU).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def default_interpret() -> bool:
    """Interpret the Pallas kernels off TPU; never on TPU."""
    return not on_tpu()


# the kernel modules import default_interpret from here, so they are
# imported after it is defined
from repro.kernels.fake_quant import fake_quant_pallas  # noqa: E402
from repro.kernels.flash_attention import flash_attention  # noqa: E402
from repro.kernels.int8_matmul import int8_matmul, quantize_weights_int8  # noqa: E402
from repro.kernels.rg_lru import rglru_pallas  # noqa: E402


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "gamma", "zeta", "q_offset",
    "block_q", "block_kv"))
def mha_flash(
    q: jax.Array,            # (B, T, H, D)
    k: jax.Array,            # (B, S, Hkv, D)
    v: jax.Array,
    gate_pi: Optional[jax.Array] = None,   # (B, T, H)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    gamma: float = 0.0,
    zeta: float = 1.0,
    q_offset: int = 0,
    block_q: int = 128,
    block_kv: int = 128,
) -> jax.Array:
    """Model-layout adapter: GQA expand + (B,H) flatten + kernel."""
    b, t, h, d = q.shape
    s, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    gf = None if gate_pi is None else gate_pi.transpose(0, 2, 1).reshape(b * h, t)
    out = flash_attention(qf, kf, vf, gf, causal=causal, window=window,
                          softcap=softcap, gamma=gamma, zeta=zeta,
                          q_offset=q_offset, block_q=block_q,
                          block_kv=block_kv)
    return out.reshape(b, h, t, d).transpose(0, 2, 1, 3)


@jax.jit
def linear_w8a8(x: jax.Array, w_q: jax.Array, w_scale: jax.Array) -> jax.Array:
    """(..., K) x int8 (K, N) -> (..., N) f32 via the int8 MXU kernel."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    y = int8_matmul(x2, w_q, w_scale)
    return y.reshape(*lead, w_q.shape[1])


def fake_quant_op(x: jax.Array, s: float, z: float, bits: int = 8) -> jax.Array:
    return fake_quant_pallas(x, s, z, bits)


def rglru_op(a: jax.Array, b: jax.Array, h0=None):
    return rglru_pallas(a, b, h0)


__all__ = ["mha_flash", "linear_w8a8", "fake_quant_op", "rglru_op",
           "quantize_weights_int8", "on_tpu", "default_interpret"]
