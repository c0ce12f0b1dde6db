"""Multi-head attention with the paper's modifications, GQA, local windows,
logit soft-capping and qk-norm — the core op the whole model zoo shares.

Three execution paths:

  * ``dense_attention``   — materializes the (Tq, Tk) probability matrix.
    Reference semantics; used for short sequences, decode steps and as the
    oracle for the Pallas kernels.
  * ``chunked_attention`` — flash-attention-style blockwise streaming over
    KV; O(T) memory. For the *clipped* softmax the affine stretch+clip is a
    function of globally-normalized probabilities, so we run the classic
    2-pass scheme: pass 1 accumulates the online (m, Z); pass 2 applies
    stretch_and_clip per block and accumulates P·V. Vanilla softmax takes
    the 1-pass online path. This is the XLA (non-Pallas) implementation the
    dry-run lowers; `repro.kernels.flash_attention` is the TPU Pallas twin.
  * ``paged_attention``   — serving decode over a paged KV cache: K/V live
    in a global head-major block pool ``(num_blocks, Hkv, block_size, Dh)``
    and each batch row owns a *block table* of physical block ids. A
    dispatcher over two backends: the fused Pallas TPU kernel
    (``repro.kernels.paged_attention``) that reads pool blocks in place
    through a scalar-prefetched block table, several blocks of every KV
    head per grid step and no step past a row's live blocks (default on
    TPU), and
    ``paged_attention_gather`` — the XLA oracle that gathers the row's
    virtual KV sequence block-by-block, then masks per block: unallocated
    table entries (id < 0) contribute nothing, and the usual causal/window
    mask over *logical* positions hides any garbage in the partially-filled
    tail block. See ``docs/serving.md``.

Layout convention: q (B, Tq, Hq, Dh); k/v (B, Tk, Hkv, Dh) with
Hq = G * Hkv (grouped-query attention).

The clipped softmax's gamma is ``AttentionConfig.gamma``: a static value,
or -alpha / ``seq_len`` where ``seq_len`` is the length the model is
trained at (``ModelConfig.attn_cfg`` passes ``train_seq_len``). No path
resolves it from the KV axis it happens to read, so a training step, a
cache-free forward, a dense cache and a paged pool of any width or live
prefix clip alike.

The ``q_offset`` vector contract (introduced with the per-slot-position
decode engine, PR 1): everywhere a query block is positioned inside the full
sequence — ``make_attention_mask``, the chunked masks, ``dense_attention``
and ``paged_attention`` — the offset may be either a shared python/scalar
position or a per-row ``(B,)`` int32 vector. With a vector, masks acquire a
leading batch dimension ``(B, Tq, Tk)`` and every row attends at its own
absolute position; this is what lets the continuous batcher decode a batch
whose rows sit at unrelated sequence positions in ONE fused step.

Both paged backends already accept Tq > 1 query blocks per row, which is
the read half of speculative decoding: a verifying tick reads k+1 query
positions against the row's whole cached prefix in one paged read. The
causal mask over LOGICAL positions is what makes that sound — any
stale entry a rejected draft left at position p is invisible to every
query with q_pos < p, and by the time a query reaches p the entry has
been rewritten (bit-identically) by the token actually banked there.
See ``serving.decode.make_spec_step`` for the full argument.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.softmax import (
    ClippedSoftmaxConfig,
    softcap,
    softmax,
    stretch_and_clip,
)

Array = jax.Array

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class AttentionConfig:
    n_heads: int
    n_kv_heads: int
    d_head: int
    causal: bool = True
    window: Optional[int] = None            # local attention window (tokens back)
    logit_softcap: Optional[float] = None   # gemma-2 style tanh cap
    softmax: ClippedSoftmaxConfig = ClippedSoftmaxConfig()
    chunk_size: int = 512                   # KV block for the chunked path
    seq_len: Optional[int] = None           # T of gamma = -alpha / T

    @property
    def group_size(self) -> int:
        assert self.n_heads % self.n_kv_heads == 0
        return self.n_heads // self.n_kv_heads

    @property
    def gamma(self) -> float:
        """The clipped softmax's lower stretch (see the module docstring)."""
        return self.softmax.resolve_gamma(self.seq_len)


def make_attention_mask(
    q_len: int,
    kv_len: int,
    causal: bool,
    window: Optional[int] = None,
    q_offset=0,
    dtype=jnp.bool_,
) -> Array:
    """Boolean attention mask, True = may attend.

    ``q_offset`` positions the query block inside the full sequence — used
    both by chunked attention and by decode (q_offset = cache position). It
    may be a scalar (shared position, returns (q_len, kv_len)) or a per-row
    (B,) vector (slot-pool decode, returns (B, q_len, kv_len)).
    """
    off = jnp.asarray(q_offset, jnp.int32)
    q_pos = (off[..., None] + jnp.arange(q_len))[..., :, None]   # (..., Tq, 1)
    k_pos = jnp.arange(kv_len)                                   # (Tk,)
    mask = jnp.ones(q_pos.shape[:-1] + (kv_len,), dtype=jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    return mask.astype(dtype)


def _expand_kv(k: Array, group: int) -> Array:
    """(B, S, Hkv, D) -> (B, S, Hkv, G, D) broadcast view for GQA einsums."""
    return jnp.broadcast_to(
        k[:, :, :, None, :], (*k.shape[:3], group, k.shape[-1])
    )


def attention_logits(q: Array, k: Array, cfg: AttentionConfig) -> Array:
    """(B, Tq, Hkv, G, Tk) scaled and (optionally) soft-capped logits."""
    b, tq, hq, d = q.shape
    g = cfg.group_size
    qg = q.reshape(b, tq, cfg.n_kv_heads, g, d)
    scale = d ** -0.5
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", (qg * scale).astype(jnp.float32), k.astype(jnp.float32))
    return softcap(logits, cfg.logit_softcap)


def dense_attention(
    q: Array,
    k: Array,
    v: Array,
    cfg: AttentionConfig,
    mask: Optional[Array] = None,
    q_offset=0,
    gate_pi: Optional[Array] = None,
) -> Array:
    """Reference attention. Returns (B, Tq, Hq, Dh).

    ``mask``: optional (Tq, Tk) shared or (B, Tq, Tk) per-row boolean.
    ``q_offset``: scalar or per-row (B,) query offset (slot-pool decode).
    ``gate_pi``: optional (B, Tq, Hq) gating probabilities (paper Eq. 5).
    """
    b, tq, hq, d = q.shape
    tk = k.shape[1]
    logits = attention_logits(q, k, cfg)               # (B, Hkv, G, Tq, Tk)
    if mask is None:
        mask = make_attention_mask(tq, tk, cfg.causal, cfg.window, q_offset)
    if mask.ndim == 3:                                 # per-row (B, Tq, Tk)
        mask = mask[:, None, None]
    mask_b = jnp.broadcast_to(mask.astype(jnp.bool_), logits.shape) if mask.ndim < 5 else mask

    sm = cfg.softmax
    if sm.is_vanilla:
        probs = softmax(logits, axis=-1, where=mask_b)
    else:
        probs = softmax(logits, axis=-1, where=mask_b)
        probs = stretch_and_clip(probs, cfg.gamma, sm.zeta)
        # clipped probabilities of masked entries are clip(gamma,0,1)=0 since
        # softmax emitted 0 there and gamma <= 0; nothing extra needed.
    probs = probs.astype(v.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    out = out.reshape(b, tq, hq, d)
    if gate_pi is not None:
        out = out * gate_pi[..., None].astype(out.dtype)
    return out


def _chunk_mask(idx, c, tk, tq, q_offset, cfg: AttentionConfig) -> Array:
    """Validity mask of one KV chunk: (Tq, c) for a scalar ``q_offset``,
    (B, Tq, c) for a per-row vector offset."""
    off = jnp.asarray(q_offset, jnp.int32)
    q_pos = (off[..., None] + jnp.arange(tq))[..., :, None]      # (..., Tq, 1)
    k_pos = idx * c + jnp.arange(c)
    mask = jnp.broadcast_to(k_pos < tk, q_pos.shape[:-1] + (c,))  # padding
    if cfg.causal:
        mask &= k_pos <= q_pos
    if cfg.window is not None:
        mask &= k_pos > q_pos - cfg.window
    return mask


def _lift_mask(mask: Array) -> Array:
    """Lift a (Tq, c) / (B, Tq, c) mask against (B, Hkv, G, Tq, c) logits."""
    return mask[None, None, None] if mask.ndim == 2 else mask[:, None, None]


def _online_pass(q, k, v, cfg: AttentionConfig, q_offset) -> Tuple[Array, Array, Array]:
    """1-pass online softmax over KV chunks. Returns (acc, m, z) where
    acc = sum exp(s - m) v, per query. Shapes:
      acc (B, Hkv, G, Tq, D); m, z (B, Hkv, G, Tq)."""
    b, tq, hq, d = q.shape
    g = cfg.group_size
    hkv = cfg.n_kv_heads
    c = cfg.chunk_size
    tk = k.shape[1]
    n_chunks = (tk + c - 1) // c
    pad = n_chunks * c - tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(b, n_chunks, c, hkv, d)
    vc = v.reshape(b, n_chunks, c, hkv, d)
    qg = (q * d ** -0.5).reshape(b, tq, hkv, g, d).astype(jnp.float32)

    def body(carry, blk):
        acc, m, z = carry
        kb, vb, idx = blk
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kb.astype(jnp.float32))
        s = softcap(s, cfg.logit_softcap)
        mask = _chunk_mask(idx, c, tk, tq, q_offset, cfg)
        s = jnp.where(_lift_mask(mask), s, NEG_INF)
        m_blk = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        z_new = z * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p, vb.astype(jnp.float32)
        )
        return (acc_new, m_new, z_new), None

    acc0 = jnp.zeros((b, hkv, g, tq, d), jnp.float32)
    m0 = jnp.full((b, hkv, g, tq), NEG_INF, jnp.float32)
    z0 = jnp.zeros((b, hkv, g, tq), jnp.float32)
    kc_t = jnp.moveaxis(kc, 1, 0)
    vc_t = jnp.moveaxis(vc, 1, 0)
    (acc, m, z), _ = jax.lax.scan(
        body, (acc0, m0, z0), (kc_t, vc_t, jnp.arange(n_chunks))
    )
    return acc, m, z


def _clipped_second_pass(q, k, v, m, z, cfg: AttentionConfig, q_offset) -> Array:
    """Pass 2 for clipped softmax: accumulate clip((z-g)·p + g)·V blockwise."""
    b, tq, hq, d = q.shape
    g = cfg.group_size
    hkv = cfg.n_kv_heads
    c = cfg.chunk_size
    tk = k.shape[1]
    gamma = cfg.gamma
    zeta = cfg.softmax.zeta
    n_chunks = (tk + c - 1) // c
    pad = n_chunks * c - tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = jnp.moveaxis(k.reshape(b, n_chunks, c, hkv, d), 1, 0)
    vc = jnp.moveaxis(v.reshape(b, n_chunks, c, hkv, d), 1, 0)
    qg = (q * d ** -0.5).reshape(b, tq, hkv, g, d).astype(jnp.float32)
    z_safe = jnp.maximum(z, jnp.finfo(jnp.float32).tiny)

    def body(acc, blk):
        kb, vb, idx = blk
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kb.astype(jnp.float32))
        s = softcap(s, cfg.logit_softcap)
        mask = _chunk_mask(idx, c, tk, tq, q_offset, cfg)
        p = jnp.exp(s - m[..., None]) / z_safe[..., None]
        p = stretch_and_clip(p, gamma, zeta)
        p = jnp.where(_lift_mask(mask), p, 0.0)
        return acc + jnp.einsum("bhgqk,bkhd->bhgqd", p, vb.astype(jnp.float32)), None

    acc0 = jnp.zeros((b, hkv, g, tq, d), jnp.float32)
    acc, _ = jax.lax.scan(body, acc0, (kc, vc, jnp.arange(n_chunks)))
    return acc


def chunked_attention(
    q: Array,
    k: Array,
    v: Array,
    cfg: AttentionConfig,
    q_offset=0,
    gate_pi: Optional[Array] = None,
) -> Array:
    """Flash-style O(T)-memory attention with vanilla OR clipped softmax."""
    b, tq, hq, d = q.shape
    acc, m, z = _online_pass(q, k, v, cfg, q_offset)
    if cfg.softmax.is_vanilla:
        out = acc / jnp.maximum(z, jnp.finfo(jnp.float32).tiny)[..., None]
    else:
        out = _clipped_second_pass(q, k, v, m, z, cfg, q_offset)
    out = jnp.moveaxis(out, 3, 1).reshape(b, tq, hq, d).astype(v.dtype)
    if gate_pi is not None:
        out = out * gate_pi[..., None].astype(out.dtype)
    return out


def paged_attention_gather(
    q: Array,
    k_pool: Array,
    v_pool: Array,
    block_table: Array,
    cfg: AttentionConfig,
    q_offset=0,
    gate_pi: Optional[Array] = None,
    live_widths: Optional[Array] = None,
    k_scale: Optional[Array] = None,
    v_scale: Optional[Array] = None,
) -> Array:
    """Gather-based attention over a paged KV cache. Returns (B, Tq, Hq, Dh).

    The XLA reference/oracle path: each row's blocks are gathered and
    flattened into a (B, W*block_size, Hkv, Dh) virtual KV sequence indexed
    by *logical* position, so the standard causal/window mask built from
    ``q_offset`` (scalar or per-row (B,) vector) applies unchanged; a
    per-block validity mask additionally hides unallocated entries (id < 0).
    Masked positions contribute exact zeros to the softmax, so the result is
    bitwise identical to dense attention over a contiguous cache of the same
    length W*block_size holding the same tokens.

    ``live_widths`` ((B,) int32, optional): each row's OWN count of live
    block-table entries. Entries at or beyond a row's count are treated as
    unallocated — their pool gather is redirected to block 0 and the
    gathered lanes are zeroed, so the per-row read is confined to the
    row's live prefix instead of the batch max. Allocation is prefix-dense,
    so those entries are ``-1`` in real schedules and masking them is
    bitwise-neutral; the mask makes the row's valid work (and, with a
    sliced table, its gather) track the row rather than the widest row in
    the tick.

    ``k_scale``/``v_scale`` ((num_blocks, 1, block_size) f32, optional):
    the int8 pool's per-slot scale rows. Dequantization is fused into the
    same block gather — scales are gathered with the identical ``safe``
    indices and multiplied back before the softmax, so the virtual KV
    sequence the mask sees is already fp. Stale scales in recycled blocks
    are hidden by the same validity/causal masks as stale KV."""
    b, w = block_table.shape
    nb, hkv, bs, dh = k_pool.shape
    tq, tk = q.shape[1], w * bs
    valid_entry = block_table >= 0                               # (B, W)
    if live_widths is not None:
        valid_entry &= jnp.arange(w)[None, :] < \
            jnp.asarray(live_widths, jnp.int32)[:, None]
    safe = jnp.where(valid_entry, jnp.clip(block_table, 0, nb - 1), 0)

    def gather(pool):            # (B, W, Hkv, BS, Dh) -> (B, W*BS, Hkv, Dh)
        return pool[safe].transpose(0, 1, 3, 2, 4).reshape(b, tk, hkv, dh)

    k, v = gather(k_pool), gather(v_pool)
    if k_scale is not None:
        ks = k_scale[safe].reshape(b, tk)
        k = k.astype(jnp.float32) * ks[:, :, None, None]
    if v_scale is not None:
        vs = v_scale[safe].reshape(b, tk)
        v = v.astype(jnp.float32) * vs[:, :, None, None]
    valid = jnp.repeat(valid_entry, bs, axis=1)                  # (B, Tk)
    if live_widths is not None:
        # dead lanes are already masked out of the softmax below; zeroing
        # the gathered values too keeps every dead-lane flop an exact zero
        zmask = valid[:, :, None, None]
        k = jnp.where(zmask, k, jnp.zeros((), k.dtype))
        v = jnp.where(zmask, v, jnp.zeros((), v.dtype))
    mask = make_attention_mask(tq, tk, cfg.causal, cfg.window, q_offset)
    mask = jnp.broadcast_to(mask, (b, tq, tk)) & valid[:, None, :]
    return dense_attention(q, k, v, cfg, mask=mask, gate_pi=gate_pi)


def paged_attention(
    q: Array,
    k_pool: Array,
    v_pool: Array,
    block_table: Array,
    cfg: AttentionConfig,
    q_offset=0,
    gate_pi: Optional[Array] = None,
    *,
    live_width: Optional[int] = None,
    live_widths: Optional[Array] = None,
    k_scale: Optional[Array] = None,
    v_scale: Optional[Array] = None,
    backend: str = "auto",
    interpret: Optional[bool] = None,
) -> Array:
    """Paged-KV attention dispatcher. Returns (B, Tq, Hq, Dh).

    ``k_pool``/``v_pool``: (num_blocks, Hkv, block_size, Dh) global pools
    shared by every batch row, head-major so the kernel reads one
    (block_size, Dh) tile per step. ``block_table``: (B, W) int32 physical
    block ids; entry j maps the row's logical token range
    [j*block_size, (j+1)*block_size) onto pool block ``block_table[b, j]``,
    with -1 marking an unallocated entry.

    Two backends:

      * ``"kernel"`` — the fused Pallas TPU kernel
        (``repro.kernels.paged_attention``): pool blocks are read in place
        through a scalar-prefetched block table; no gather, no materialized
        virtual sequence. Its grid is ``(B, head_groups, ceil(W / P))``:
        one step reads P consecutive table entries (P derived from the
        block size: enough keys for 128 lanes, 8 at block 16) of every KV
        head unless VMEM forces head groups.
      * ``"gather"`` — ``paged_attention_gather``, the XLA path that
        materializes each row's virtual KV sequence. Bitwise-equal to dense
        attention; the oracle the kernel is swept against.

    ``backend="auto"`` follows ``kernels.ops.default_interpret()``: the
    kernel where it runs compiled (TPU), the gather path where it would
    only be interpreted. ``interpret`` (tests only) overrides the kernel's
    mode.

    ``live_width``: optional static number of block-table entries actually
    in use (allocation is prefix-dense — the scheduler fills tables from
    entry 0). When given, only ``table[:, :live_width]`` is visited by
    EITHER backend, making the per-tick cost proportional to live tokens
    instead of the table width W. Positions beyond the live prefix are
    causally unreachable and gamma does not depend on the KV axis, so
    slicing is exact, not an approximation.

    ``live_widths``: optional (B,) int32 vector of each row's OWN live
    entry count, confining each row's read to its own prefix rather than
    the tick max (the shapes stay static — ``live_width`` bounds them,
    ``live_widths`` confines the valid work inside them). The gather path
    masks the entries past it (see ``paged_attention_gather``). The kernel
    bounds each row at min(``live_widths[b]``, the causal bound
    ceil((q_offset[b] + Tq) / block_size)) entries: its grid steps past
    that bound fetch nothing and compute nothing
    (``kernels.paged_attention.live_bound``).

    ``k_scale``/``v_scale``: per-slot scale rows (num_blocks, 1,
    block_size) of an int8 pool (``init_paged_cache(kv_int8=True)``).
    Both backends fuse dequantization into their block reads: the gather
    path gathers scales alongside blocks, the kernel DMAs each block's
    scale row through the same table-driven index_map. Scale arrays are
    pool-indexed, not table-indexed, so ``live_width`` slicing leaves them
    untouched.
    """
    w_full = block_table.shape[1]
    if live_width is not None:
        block_table = block_table[:, :max(1, min(int(live_width), w_full))]
    if backend == "auto":
        from repro.kernels.ops import default_interpret
        backend = "gather" if default_interpret() else "kernel"
    if backend == "kernel":
        from repro.kernels.paged_attention import paged_mha
        sm = cfg.softmax
        gamma, zeta = (0.0, 1.0) if sm.is_vanilla else (cfg.gamma, sm.zeta)
        return paged_mha(q, k_pool, v_pool, block_table, q_offset, gate_pi,
                         causal=cfg.causal, window=cfg.window,
                         softcap=cfg.logit_softcap, gamma=gamma, zeta=zeta,
                         k_scale=k_scale, v_scale=v_scale,
                         live_widths=live_widths, interpret=interpret)
    if backend != "gather":
        raise ValueError(f"unknown paged-attention backend {backend!r}")
    return paged_attention_gather(q, k_pool, v_pool, block_table, cfg,
                                  q_offset=q_offset, gate_pi=gate_pi,
                                  live_widths=live_widths,
                                  k_scale=k_scale, v_scale=v_scale)


def attention(
    q: Array,
    k: Array,
    v: Array,
    cfg: AttentionConfig,
    q_offset=0,
    gate_pi: Optional[Array] = None,
    force_dense: bool = False,
) -> Array:
    """Dispatcher: dense for small problems / decode, chunked for long T.

    Routing (pinned by tests/test_attention.py::test_dispatcher_routing):
    dense when forced, when decoding (tq == 1) with tk <= 8192, or when
    tq > 1 and tq*tk <= 2048^2; chunked otherwise (long-T prefill/training
    and long-context decode). The seed's condition chained these with an
    unparenthesized ``... or tq == 1 and tk <= 8192`` — the precedence trap
    this explicit form replaces — and ``force_dense`` did not actually
    force for large tq*tk.
    """
    tq, tk = q.shape[1], k.shape[1]
    if force_dense or (tq == 1 and tk <= 8192) or (tq > 1 and tq * tk <= 2048 * 2048):
        return dense_attention(q, k, v, cfg, q_offset=q_offset, gate_pi=gate_pi)
    return chunked_attention(q, k, v, cfg, q_offset=q_offset, gate_pi=gate_pi)
