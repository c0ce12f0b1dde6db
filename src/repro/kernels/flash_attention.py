"""Pallas TPU flash attention with clipped softmax + gated attention.

TPU adaptation of the paper's drop-in softmax replacement (DESIGN.md §3):
the clipped softmax needs the *globally normalized* probability before the
affine stretch+clip, which conflicts with single-pass online softmax (you
never hold the final (m, Z) while streaming). We therefore run TWO
streaming passes over KV blocks:

  pass 1 (``_mz_kernel``)  — classic online-softmax recurrence, emits the
      per-query (m, Z); O(T) memory, never materializes (Tq, Tk).
  pass 2 (``_av_kernel``)  — re-streams KV, forms
      p = clip((zeta-gamma) * exp(s-m)/Z + gamma, 0, 1) per block and
      accumulates p @ V in an f32 VMEM scratch.

Vanilla softmax (gamma=0, zeta=1) takes the standard single-pass kernel
with running rescale. The paper's per-(head, token) gate pi multiplies the
output tile in the epilogue (token-local, fuses for free).

Grid: (batch*heads, nQ, nKV); the KV dimension is sequential so VMEM
scratch carries across KV steps ("arbitrary" dimension semantics on TPU).
Blocks (block_q x d_head), (block_kv x d_head): multiples of 128 keep MXU
matmul dims aligned; VMEM working set per step = q + k + v blocks + acc
~= 4 * 128 * 256 * 4B ~ 0.5 MB at d_head=256 — far under the ~16 MB/core
budget, leaving headroom for the double-buffered pipeline.

Oracle: ``repro.kernels.ref.attention_ref`` (pure jnp); swept over shapes,
dtypes, masks and (gamma, zeta) in tests/test_kernels.py.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import default_interpret

NEG_INF = -1e30


def _block_mask(q_idx, kv_idx, block_q, block_kv, causal, window, q_offset, kv_len):
    q_pos = q_idx * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 0) + q_offset
    k_pos = kv_idx * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 1)
    mask = k_pos < kv_len
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    return mask


def _masked_scores(q_ref, k_ref, scale, softcap, block_q, block_kv,
                   causal, window, q_offset, kv_len):
    s = jax.lax.dot_general(
        q_ref[0].astype(jnp.float32), k_ref[0].astype(jnp.float32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    mask = _block_mask(pl.program_id(1), pl.program_id(2), block_q, block_kv,
                       causal, window, q_offset, kv_len)
    return jnp.where(mask, s, NEG_INF), mask


def _clean_v(v_ref, kv_idx, block_kv, kv_len):
    """Zero out-of-range V rows: block padding may be NaN (interpret mode
    fills OOB with NaN) and 0 * NaN = NaN in the p @ V accumulation."""
    valid = kv_idx * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, v_ref[0].shape, 0) < kv_len
    return jnp.where(valid, v_ref[0].astype(jnp.float32), 0.0)


def _mz_kernel(q_ref, k_ref, m_ref, z_ref, m_scr, z_scr, *, cfg):
    kv_idx = pl.program_id(2)

    @pl.when(kv_idx == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        z_scr[...] = jnp.zeros_like(z_scr)

    s, mask = _masked_scores(q_ref, k_ref, cfg["scale"], cfg["softcap"],
                             cfg["block_q"], cfg["block_kv"], cfg["causal"],
                             cfg["window"], cfg["q_offset"], cfg["kv_len"])
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
    z_scr[...] = z_scr[...] * jnp.exp(m_prev - m_new) + jnp.sum(p, axis=-1)
    m_scr[...] = m_new

    @pl.when(kv_idx == cfg["n_kv"] - 1)
    def _():
        m_ref[0] = m_scr[...]
        z_ref[0] = z_scr[...]


def _av_kernel(q_ref, k_ref, v_ref, m_ref, z_ref, gate_ref, o_ref, acc_scr,
               *, cfg):
    kv_idx = pl.program_id(2)

    @pl.when(kv_idx == 0)
    def _():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    s, mask = _masked_scores(q_ref, k_ref, cfg["scale"], cfg["softcap"],
                             cfg["block_q"], cfg["block_kv"], cfg["causal"],
                             cfg["window"], cfg["q_offset"], cfg["kv_len"])
    m = m_ref[0]
    z = jnp.maximum(z_ref[0], 1e-30)
    p = jnp.exp(s - m[:, None]) / z[:, None]
    p = jnp.clip((cfg["zeta"] - cfg["gamma"]) * p + cfg["gamma"], 0.0, 1.0)
    p = jnp.where(mask, p, 0.0)
    acc_scr[...] += jax.lax.dot_general(
        p, _clean_v(v_ref, kv_idx, cfg["block_kv"], cfg["kv_len"]),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(kv_idx == cfg["n_kv"] - 1)
    def _():
        out = acc_scr[...]
        if gate_ref is not None:
            out = out * gate_ref[0][:, None]
        o_ref[0] = out.astype(o_ref.dtype)


def _vanilla_kernel(q_ref, k_ref, v_ref, gate_ref, o_ref, m_scr, z_scr,
                    acc_scr, *, cfg):
    kv_idx = pl.program_id(2)

    @pl.when(kv_idx == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        z_scr[...] = jnp.zeros_like(z_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    s, mask = _masked_scores(q_ref, k_ref, cfg["scale"], cfg["softcap"],
                             cfg["block_q"], cfg["block_kv"], cfg["causal"],
                             cfg["window"], cfg["q_offset"], cfg["kv_len"])
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
    z_scr[...] = z_scr[...] * corr + jnp.sum(p, axis=-1)
    acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
        p, _clean_v(v_ref, kv_idx, cfg["block_kv"], cfg["kv_len"]),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(kv_idx == cfg["n_kv"] - 1)
    def _():
        out = acc_scr[...] / jnp.maximum(z_scr[...], 1e-30)[:, None]
        if gate_ref is not None:
            out = out * gate_ref[0][:, None]
        o_ref[0] = out.astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,            # (BH, Tq, Dh) — batch*heads flattened
    k: jax.Array,            # (BH, Tk, Dh)
    v: jax.Array,            # (BH, Tk, Dh)
    gate_pi: Optional[jax.Array] = None,    # (BH, Tq)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    gamma: float = 0.0,
    zeta: float = 1.0,
    q_offset: int = 0,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused multi-head attention; (gamma, zeta) = (0, 1) selects the
    single-pass vanilla path, anything else the two-pass clipped path."""
    if interpret is None:
        interpret = default_interpret()
    bh, tq, dh = q.shape
    tk = k.shape[1]
    block_q = min(block_q, tq)
    block_kv = min(block_kv, tk)
    n_q = pl.cdiv(tq, block_q)
    n_kv = pl.cdiv(tk, block_kv)
    grid = (bh, n_q, n_kv)
    cfg = dict(block_q=block_q, block_kv=block_kv, scale=dh ** -0.5,
               causal=causal, window=window, softcap=softcap,
               q_offset=q_offset, kv_len=tk, n_kv=n_kv,
               gamma=gamma, zeta=zeta)

    q_spec = pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0))
    kv_spec = pl.BlockSpec((1, block_kv, dh), lambda b, i, j: (b, j, 0))
    o_spec = pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0))
    mz_spec = pl.BlockSpec((1, block_q), lambda b, i, j: (b, i))
    has_gate = gate_pi is not None

    if gamma == 0.0 and zeta == 1.0:
        if has_gate:
            kern = functools.partial(_vanilla_kernel, cfg=cfg)
            in_specs = [q_spec, kv_spec, kv_spec, mz_spec]
            args = (q, k, v, gate_pi)
        else:
            kern = functools.partial(
                lambda qr, kr, vr, o, m, z, a, cfg: _vanilla_kernel(
                    qr, kr, vr, None, o, m, z, a, cfg=cfg), cfg=cfg)
            in_specs = [q_spec, kv_spec, kv_spec]
            args = (q, k, v)
        return pl.pallas_call(
            kern, grid=grid, in_specs=in_specs, out_specs=o_spec,
            out_shape=jax.ShapeDtypeStruct((bh, tq, dh), q.dtype),
            scratch_shapes=[pltpu.VMEM((block_q,), jnp.float32),
                            pltpu.VMEM((block_q,), jnp.float32),
                            pltpu.VMEM((block_q, dh), jnp.float32)],
            interpret=interpret,
        )(*args)

    # ---- clipped softmax: 2 streaming passes ----
    m, z = pl.pallas_call(
        functools.partial(_mz_kernel, cfg=cfg),
        grid=grid,
        in_specs=[q_spec, kv_spec],
        out_specs=[mz_spec, mz_spec],
        out_shape=[jax.ShapeDtypeStruct((bh, tq), jnp.float32),
                   jax.ShapeDtypeStruct((bh, tq), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_q,), jnp.float32),
                        pltpu.VMEM((block_q,), jnp.float32)],
        interpret=interpret,
    )(q, k)

    if has_gate:
        kern = functools.partial(_av_kernel, cfg=cfg)
        in_specs = [q_spec, kv_spec, kv_spec, mz_spec, mz_spec, mz_spec]
        args = (q, k, v, m, z, gate_pi)
    else:
        kern = functools.partial(
            lambda qr, kr, vr, mr, zr, o, a, cfg: _av_kernel(
                qr, kr, vr, mr, zr, None, o, a, cfg=cfg), cfg=cfg)
        in_specs = [q_spec, kv_spec, kv_spec, mz_spec, mz_spec]
        args = (q, k, v, m, z)
    return pl.pallas_call(
        kern, grid=grid, in_specs=in_specs, out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((bh, tq, dh), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, dh), jnp.float32)],
        interpret=interpret,
    )(*args)
