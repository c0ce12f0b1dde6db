"""Pure-jnp oracles for every Pallas kernel. Slow, obviously-correct,
materializing implementations — the tests sweep shapes/dtypes and assert
allclose against these."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def attention_ref(q, k, v, gate_pi=None, *, causal=True, window=None,
                  softcap=None, gamma=0.0, zeta=1.0, q_offset=0):
    """(BH, Tq, Dh) x (BH, Tk, Dh) -> (BH, Tq, Dh). Materializes (Tq, Tk)."""
    bh, tq, dh = q.shape
    tk = k.shape[1]
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * dh ** -0.5
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    q_pos = jnp.arange(tq)[:, None] + q_offset
    k_pos = jnp.arange(tk)[None, :]
    mask = jnp.ones((tq, tk), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = jnp.where(mask, p, 0.0)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    if not (gamma == 0.0 and zeta == 1.0):
        p = jnp.clip((zeta - gamma) * p + gamma, 0.0, 1.0)
        p = jnp.where(mask, p, 0.0)
    out = jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32))
    if gate_pi is not None:
        out = out * gate_pi.astype(jnp.float32)[..., None]
    return out.astype(q.dtype)


def int8_matmul_ref(x, w_q, w_scale, *, x_bits=8):
    """W8A8 matmul oracle: dynamic per-tensor asymmetric activation
    quantization, symmetric int8 weights.

    x: (M, K) float; w_q: (K, N) int8; w_scale: scalar f32.
    Returns (M, N) f32 = dequant(q(x)) @ (w_q * w_scale)."""
    n = 2 ** x_bits
    x32 = x.astype(jnp.float32)
    x_min = jnp.minimum(jnp.min(x32), 0.0)
    x_max = jnp.maximum(jnp.max(x32), 0.0)
    s = jnp.maximum((x_max - x_min) / (n - 1), 1e-8)
    z = jnp.clip(jnp.round(-x_min / s), 0, n - 1)
    xq = jnp.clip(jnp.round(x32 / s) + z, 0, n - 1) - z   # integer grid, f32
    return (xq * s) @ (w_q.astype(jnp.float32) * w_scale)


def fake_quant_ref(x, s, z, bits=8):
    """Eq. 1 fake-quant oracle (per-tensor)."""
    n = 2 ** bits
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s + z), 0, n - 1)
    return (s * (q - z)).astype(x.dtype)


def rglru_ref(a, b, h0=None):
    """Sequential linear recurrence h_t = a_t * h_{t-1} + b_t.

    a, b: (B, T, D) f32; h0 (B, D) or None. Returns (h (B,T,D), h_last)."""
    bsz, t, d = a.shape
    h = jnp.zeros((bsz, d), jnp.float32) if h0 is None else h0.astype(jnp.float32)
    outs = []
    for i in range(t):
        h = a[:, i] * h + b[:, i]
        outs.append(h)
    hs = jnp.stack(outs, axis=1)
    return hs, h
