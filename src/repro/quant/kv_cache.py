"""Int8 paged-KV quantization: per-block scale vectors for the block pools.

The paged serving cache (``models.transformer.init_paged_cache``) stores K/V
in global block pools ``(num_blocks, Hkv, block_size, Dh)``. With
``kv_int8=True`` the pools hold int8 and each pool block carries a *scale
row* ``(num_blocks, 1, block_size)`` — one f32 scale per token slot of the
block, symmetric int8 over that token's (Hkv, Dh) values:

    scale[nb, s] = max|kv[nb, s]| / 127        q = round(kv / scale)

Why one scale per slot instead of one scalar per block: a block fills
incrementally (chunked prefill writes a few tokens per tick), so a scalar
block scale would have to GROW as larger tokens arrive, requantizing the
already-written int8 values. That requantization chain depends on how the
prompt was chunked — it would break the engine's bitwise-invariance
contracts (chunk size, slot assignment, preemption-resume; see
tests/test_chunked_prefill.py) — and a recycled block would inherit the
previous occupant's amax. Per-slot scales make quantization write-once:
each token is quantized exactly once from its fp value in the same masked
scatter that writes the pool, so the stored bits are a pure function of
(token value, logical position) — the same staleness argument that lets
recycled blocks keep garbage KV applies verbatim to garbage scales. The
scale vector still lives and travels *per block* (it rides the block-table
DMA next to its pool block in the Pallas kernel), at 4 bytes per slot
against ``Hkv * Dh`` bytes of int8 payload.

Swapped preemption gets the same guarantee for free: the scale vectors are
batch-free *pool* leaves exactly like the int8 K/V pools, so the
scheduler's swap-out copies a victim's scale rows to host alongside its
blocks and swap-in restores both into freshly allocated block ids
(``serving.scheduler.SwappedState``). Because the stored bits are already
a pure function of (token value, logical position), a swap round-trip is
bit-identical to never having been preempted — which is what lets
tests/test_slo_serving.py assert swap-resume == recompute-resume ==
unpreempted, bitwise, on the int8-KV engine.

Prefix sharing (``serving.prefix_cache``) rides on exactly this choice: a
cached prompt block can be mapped into ANOTHER request's block table only
because its int8 bits + per-token scales depend on nothing but the tokens
and positions the trie keys it by. A scalar per-block scale would have
made shared blocks owner-history-dependent (whoever wrote last set the
amax) and copy-on-write divergence lossy; per-slot scales make a shared
read bitwise-equal to the cold prefill it replaced, and a CoW block copy
(``models.transformer.copy_pool_blocks``) is exact because the scale
vector is copied verbatim alongside the int8 payload.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

Array = jax.Array

# symmetric int8 over [-127, 127]; scale floor keeps all-zero tokens exact
KV_QMAX = 127.0
KV_EPS = 1e-8


def kv_quant(x: Array) -> Tuple[Array, Array]:
    """Quantize ``(..., Hkv, Dh)`` KV values to (int8 values, (...,) scales).

    The last two axes (heads, head dim) share one scale — the per-token
    granularity of the pool's per-block scale vectors."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=(-2, -1))
    scale = jnp.maximum(amax / KV_QMAX, KV_EPS)
    q = jnp.clip(jnp.round(xf / scale[..., None, None]), -KV_QMAX, KV_QMAX
                 ).astype(jnp.int8)
    return q, scale


def kv_dequant(q: Array, scale: Array) -> Array:
    """Inverse of ``kv_quant``: (..., Hkv, Dh) int8 + (...,) scales -> f32."""
    return q.astype(jnp.float32) * scale[..., None, None].astype(jnp.float32)
