"""Basic layers: linear, norms, embeddings, rotary position embeddings.

Every layer takes an optional ``QuantContext`` + site name so the PTQ driver
can fake-quantize weights and activations exactly where integer hardware
would (inputs and outputs of every matmul — paper Section 5).
"""
from __future__ import annotations

import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.nn.module import Array, DTypePolicy, Params, normal_init
from repro.quant.qconfig import NO_QUANT, QuantContext


# --------------------------------------------------------------------------
# Linear
# --------------------------------------------------------------------------
def linear_init(
    key: Array, d_in: int, d_out: int, *, bias: bool = True,
    std: Optional[float] = None, dtype=jnp.float32,
) -> Params:
    std = std if std is not None else 1.0 / math.sqrt(d_in)
    p = {"w": normal_init(key, (d_in, d_out), std, dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def linear_apply(
    p: Params, x: Array, ctx: QuantContext = NO_QUANT, name: str = "linear",
    compute_dtype=None,
) -> Array:
    if ctx.mode == "int8" and "w_q8" in p:
        return _linear_int8_apply(p, x, ctx, name)
    w = ctx.weight(name, p["w"])
    if compute_dtype is not None:
        x = x.astype(compute_dtype)
        w = w.astype(compute_dtype)
    x = ctx.act(name + ".in", x)
    y = x @ w
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return ctx.act(name + ".out", y)


def _linear_int8_apply(p: Params, x: Array, ctx: QuantContext,
                       name: str) -> Array:
    """Hardware W8A8 path: int8 x int8 -> int32 through the MXU kernel.

    Weights come pre-quantized on the params tree
    (quant.int8_weights.attach_int8_weights); the activation range is the
    STATIC per-tensor (s, z) calibrated for this site — falling back to
    dynamic in-kernel ranging only if the site was never seen."""
    from repro.kernels.int8_matmul import int8_matmul  # avoid import cycle

    qp = ctx.act_qparams(name + ".in")
    s_x, z_x = qp if qp is not None else (None, None)
    lead = x.shape[:-1]
    y = int8_matmul(
        x.reshape(-1, x.shape[-1]), p["w_q8"], p["w_scale"],
        x_scale=s_x, x_zero=z_x)
    y = y.reshape(*lead, p["w_q8"].shape[-1])
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


# --------------------------------------------------------------------------
# Norms — f32 accumulation regardless of compute dtype
# --------------------------------------------------------------------------
def layernorm_init(d: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm_apply(p: Params, x: Array, eps: float = 1e-6,
                    ctx: QuantContext = NO_QUANT, name: str = "ln") -> Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return ctx.act(name + ".out", y.astype(dt))


def rmsnorm_init(d: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm_apply(p: Params, x: Array, eps: float = 1e-6,
                  ctx: QuantContext = NO_QUANT, name: str = "rms",
                  zero_centered: bool = False) -> Array:
    """RMSNorm; ``zero_centered=True`` uses the gemma convention
    (scale stored as gamma-1 around zero)."""
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    scale = p["scale"].astype(jnp.float32)
    if zero_centered:
        scale = scale + 1.0
    return ctx.act(name + ".out", (y * scale).astype(dt))


def norm_init(kind: str, d: int, dtype=jnp.float32) -> Params:
    return layernorm_init(d, dtype) if kind == "layernorm" else rmsnorm_init(d, dtype)


def norm_apply(kind: str, p: Params, x: Array, ctx: QuantContext = NO_QUANT,
               name: str = "norm", zero_centered: bool = False) -> Array:
    if kind == "layernorm":
        return layernorm_apply(p, x, ctx=ctx, name=name)
    return rmsnorm_apply(p, x, ctx=ctx, name=name, zero_centered=zero_centered)


# --------------------------------------------------------------------------
# Embeddings
# --------------------------------------------------------------------------
def embedding_init(key: Array, vocab: int, d: int, std: float = 0.02,
                   dtype=jnp.float32) -> Params:
    return {"table": normal_init(key, (vocab, d), std, dtype)}


def embedding_apply(p: Params, ids: Array, ctx: QuantContext = NO_QUANT,
                    name: str = "embed", scale: Optional[float] = None) -> Array:
    table = ctx.weight(name, p["table"])
    y = jnp.take(table, ids, axis=0)
    if scale is not None:
        y = y * jnp.asarray(scale, y.dtype)
    return ctx.act(name + ".out", y)


def embedding_attend(p: Params, x: Array, ctx: QuantContext = NO_QUANT,
                     name: str = "lm_head") -> Array:
    """Tied-softmax output head: logits = x @ table^T."""
    table = ctx.weight(name, p["table"])
    x = ctx.act(name + ".in", x)
    return x.astype(jnp.float32) @ table.T.astype(jnp.float32)


def positional_embedding_init(key: Array, max_len: int, d: int,
                              dtype=jnp.float32) -> Params:
    return {"table": normal_init(key, (max_len, d), 0.02, dtype)}


def positional_embedding_apply(p: Params, positions: Array) -> Array:
    return jnp.take(p["table"], positions, axis=0)


# --------------------------------------------------------------------------
# Rotary position embeddings (RoPE)
# --------------------------------------------------------------------------
def rope_angles(positions: Array, d_head: int, theta: float = 10000.0
                ) -> Tuple[Array, Array]:
    """cos/sin tables, shape (..., T, d_head/2), f32."""
    freqs = 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=jnp.float32) / d_head))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: Array, cos: Array, sin: Array) -> Array:
    """x: (B, T, H, D); cos/sin: (T, D/2) or (B, T, D/2)."""
    dt = x.dtype
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    if cos.ndim == 2:     # (T, D/2) -> broadcast over batch and heads
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:                 # (B, T, D/2)
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(dt)


# --------------------------------------------------------------------------
# Depthwise causal temporal conv (griffin / audio frontends)
# --------------------------------------------------------------------------
def conv1d_init(key: Array, d: int, width: int, dtype=jnp.float32) -> Params:
    return {
        "w": normal_init(key, (width, d), 1.0 / math.sqrt(width), dtype),
        "b": jnp.zeros((d,), dtype),
    }


def conv1d_apply(p: Params, x: Array, state: Optional[Array] = None
                 ) -> Tuple[Array, Array]:
    """Causal depthwise conv over time. x: (B, T, D).

    ``state``: (B, width-1, D) history for decode; returns (y, new_state).
    """
    w = p["w"]
    width = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], width - 1, x.shape[-1]), x.dtype)
    xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    y = jnp.zeros_like(x)
    for i in range(width):
        y = y + xp[:, i : i + x.shape[1], :] * w[i].astype(x.dtype)
    y = y + p["b"].astype(x.dtype)
    new_state = xp[:, -(width - 1):, :] if width > 1 else state
    return y, new_state
