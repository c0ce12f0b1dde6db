"""Softmax variants from the paper (Section 4.1).

The paper's central numerical object: a softmax that can emit *exact zeros*
(and ones) with a finite input dynamic range, so attention heads that want a
no-op don't have to grow activation outliers.

    clipped_softmax(x; zeta, gamma) = clip((zeta - gamma) * softmax(x) + gamma, 0, 1)

with gamma <= 0 <= 1 <= zeta (Eq. 4). Only gamma < 0 (clipping at zero)
matters empirically (paper Table 1 / Table 8); zeta defaults to 1.

`ClippedSoftmaxConfig.resolve_gamma` implements the sequence-length-robust
parameterization gamma = -alpha / T from paper Section 5.2 (alpha in [2, 4]
works across T). T is the length the model is trained at, fixed by its
config (``ModelConfig.train_seq_len``, see ``AttentionConfig.gamma``),
never the size of the KV axis at hand: a training step, a cache-free
forward, a dense cache and a paged pool of any width all clip at the same
gamma.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class ClippedSoftmaxConfig:
    """Hyper-parameters of the clipped softmax (paper Eq. 4)."""

    gamma: float = 0.0          # lower stretch, <= 0; 0 disables low clipping
    zeta: float = 1.0           # upper stretch, >= 1; 1 disables high clipping
    # If set, gamma = -alpha / T (paper Sec. 5.2) for the length T the
    # model is trained at, and the static `gamma` above is ignored.
    alpha: Optional[float] = None

    def resolve_gamma(self, seq_len: Optional[int]) -> float:
        if self.alpha is not None:
            if seq_len is None:
                raise ValueError(
                    "alpha-parameterized clipping needs the length the "
                    "model is trained at (AttentionConfig.seq_len)")
            return -float(self.alpha) / float(seq_len)
        return float(self.gamma)

    @property
    def is_vanilla(self) -> bool:
        return self.alpha is None and self.gamma == 0.0 and self.zeta == 1.0


def softmax(logits: Array, axis: int = -1, where: Optional[Array] = None) -> Array:
    """Standard softmax with optional boolean mask (True = attend)."""
    if where is not None:
        logits = jnp.where(where, logits, jnp.finfo(logits.dtype).min)
    m = jax.lax.stop_gradient(jnp.max(logits, axis=axis, keepdims=True))
    unnorm = jnp.exp(logits - m)
    if where is not None:
        unnorm = jnp.where(where, unnorm, 0.0)
    denom = jnp.sum(unnorm, axis=axis, keepdims=True)
    return unnorm / jnp.maximum(denom, jnp.finfo(logits.dtype).tiny)


def stretch_and_clip(probs: Array, gamma: float, zeta: float) -> Array:
    """Affine stretch (0,1)->(gamma,zeta) then clip back to [0,1] (Eq. 4).

    Split out so streaming/flash attention kernels can reuse the exact same
    epilogue on blockwise-normalized probabilities.
    """
    if gamma == 0.0 and zeta == 1.0:
        return probs
    y = (zeta - gamma) * probs + gamma
    return jnp.clip(y, 0.0, 1.0)


def clipped_softmax(
    logits: Array,
    gamma: float,
    zeta: float = 1.0,
    axis: int = -1,
    where: Optional[Array] = None,
) -> Array:
    """clip((zeta - gamma) * softmax(x) + gamma, 0, 1) — paper Eq. 4.

    Rows no longer sum to 1 in general; that is the point: probabilities of
    exactly 0 (and 1) are representable with finite logits, and clipped
    entries receive zero gradient so outliers stop being rewarded.
    """
    return stretch_and_clip(softmax(logits, axis=axis, where=where), gamma, zeta)


def softcap(logits: Array, cap: Optional[float]) -> Array:
    """Gemma-2 style logit soft-capping: cap * tanh(x / cap)."""
    if cap is None:
        return logits
    return cap * jnp.tanh(logits / cap)
