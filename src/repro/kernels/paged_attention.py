"""Pallas TPU paged-attention kernel: in-place block-pool reads, several
pool blocks of all KV heads per grid step, no step past a row's live blocks.

TPU twin of ``repro.core.attention.paged_attention``'s gather path,
specialized for serving ticks (decode Tq = 1, speculative Tq = k+1, and
prefill chunks) over the paged KV cache (vLLM/PagedAttention pattern).
The gather path materializes every row's virtual KV sequence — a
(B, W*block_size, Hkv, Dh) tensor per layer per tick — before attending;
this kernel never does. K/V stay in the global pools
``(num_blocks, Hkv, block_size, Dh)`` and are DMA'd from there into VMEM,
addressed through the scalar-prefetched block table
(``pltpu.PrefetchScalarGridSpec``).

Grid: ``(B, head_groups, ceil(W / P))``, the table axis innermost and
sequential, so the f32 VMEM scratch carries the online-softmax state
across a row's steps. One step reads P consecutive table entries of one
row for a group of KV heads:

  * P (``blocks_per_step``) is the fewest entries whose keys fill a
    128-lane score row, capped by W: 8 at block size 16. The grid has P
    times fewer steps than one of one block each, and each step's dot
    products are P times wider.
  * the head group is every KV head (one group) unless the step's VMEM
    working set — the scores and accumulator of Tq*G query rows per head,
    the double-buffered blocks — would not fit (``_heads_per_step``); then
    the largest divisor of Hkv that fits. The pool is head-major, so one
    pool block is one contiguous ``(Hkv, block_size, Dh)`` slab and a
    step's read of it is one DMA.

Live bound: row b visits only its first ``bound[b]`` table entries
(``live_bound``): min(its own live entry count ``live_widths[b]``, the
causal bound ceil((q_off[b] + Tq) / block_size), W) — W alone without
``live_widths``, and without the causal bound for non-causal attention.
Grid steps past the bound fetch nothing and compute nothing; a row with
no live entry writes zeros. ``live_steps`` gives each row's working
steps; the scheduler reports their sum on its dispatch span.

Fetch: each pool (and int8 scale array) is passed P times, operand i
holding entry j*P + i of step j through a BlockSpec whose index_map reads
the scalar-prefetched table (``pltpu.PrefetchScalarGridSpec``), so the
Pallas pipeline double-buffers every block DMA against the previous
step's compute. Past the last step in which its entry is inside the
row's bound, operand i keeps that step's block index, and the pipeline,
which skips a fetch whose block index did not change, reads nothing more
for the row. Entries inside the bound that are unallocated (-1) read pool
block 0, and the mask hides them. (Manual DMA with one
``make_async_copy`` per entry was the other way: Mosaic refuses a DMA
slice of a ``(.., block_size, 64)`` int8 pool in HBM, whose memref it pads
to 128 lanes.)

Semantics match the gather oracle:

  * causal + local-window masks over *logical* positions built from the
    prefetched per-row ``q_offset`` (scalar or (B,) vector);
  * unallocated table entries (id < 0) contribute nothing;
  * logit soft-capping;
  * vanilla softmax = single online pass; the paper's clipped softmax =
    the same TWO streaming passes as ``kernels/flash_attention.py``
    (pass 1 emits the per-query (m, Z), pass 2 re-streams the blocks and
    accumulates clip((zeta-gamma)·p + gamma, 0, 1) @ V). ``gamma`` is the
    caller's resolved value (``AttentionConfig.gamma``);
  * the per-head gate ``pi`` multiplies the output tile in the epilogue;
  * int8 pools (``init_paged_cache(kv_int8=True)``): a block's scale row
    rides the same table-driven index_map as its pool block. A scale is
    per key slot, so it scales a score column (K) or a probability column
    (V): ``(q @ k_q^T) * ks`` and ``(p * vs) @ v_q``, the int8 values
    entering the dot exactly. Stale scales in recycled blocks are masked
    exactly like stale KV.

Layout: the per-slot int8 scales are ``(num_blocks, 1, block_size)`` —
one lane-major row per block; a step lays its P rows side by side as one
score row — and the per-query m/z/gate vectors are ``(B, Hkv, Tq*G, 1)``
columns.
Accumulation is f32 blockwise streaming, so results match the gather
oracle to f32 round-off of the differing reduction order, not bitwise.

Oracle: ``paged_attention(..., backend="gather")``; swept over dtypes, GQA
ratios, masks, (gamma, zeta), ragged per-row positions and live widths,
partial tail blocks and table widths that are no multiple of P in
tests/test_paged_kernel.py (interpret mode on CPU), and compiled for a v5e
at OPT-125m widths in tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import default_interpret

NEG_INF = -1e30
LANES = 128
# the working set a step may plan for, and the scoped VMEM the kernel asks
# for (v5e has 128 MiB of VMEM; its default scope is 16 MiB)
VMEM_BUDGET = 24 * 2 ** 20
VMEM_LIMIT = 32 * 2 ** 20


def blocks_per_step(block_size: int, width: int) -> int:
    """P: table entries one grid step reads — the fewest whose keys fill a
    128-lane score row, and no more than the table has."""
    return max(1, min(width, -(-LANES // block_size)))


def live_bound(q_off, tq: int, live_widths=None, *, block_size: int,
               width: int, causal: bool = True):
    """Per-row count of leading table entries the kernel visits: min of
    the row's ``live_widths``, the causal bound ceil((q_off + tq) /
    block_size) and ``width``. Numpy in, numpy out (the scheduler's host
    arrays); jax in, jax out (the kernel's wrapper)."""
    xp = jnp if any(isinstance(a, jax.Array) for a in (q_off, live_widths)) \
        else np
    bound = xp.full(xp.shape(q_off), width, xp.int32)
    if live_widths is not None:
        bound = xp.minimum(bound, xp.asarray(live_widths, xp.int32))
    if causal:
        need = (xp.asarray(q_off, xp.int32) + tq + block_size - 1) \
            // block_size
        bound = xp.minimum(bound, need)
    return xp.maximum(bound, 0)


def live_steps(q_off, tq: int, live_widths=None, *, block_size: int,
               width: int, causal: bool = True):
    """Per-row number of grid steps along the table axis that fetch and
    compute: ceil(``live_bound`` / P). Its sum over rows against
    B x ceil(width / P) is the share of the grid that works."""
    p = blocks_per_step(block_size, width)
    return (live_bound(q_off, tq, live_widths, block_size=block_size,
                       width=width, causal=causal) + p - 1) // p


def _tile_bytes(shape, itemsize: int) -> int:
    """VMEM bytes of an array: its last two dims padded to the TPU tile
    ((8 * 4 / itemsize) sublanes x 128 lanes)."""
    *lead, rows, cols = shape
    sub = 8 * max(1, 4 // itemsize)
    return math.prod(lead) * (-(-rows // sub) * sub) * \
        (-(-cols // LANES) * LANES) * itemsize


def _working_set(hh, *, tq_g, dh, bs, p, kv_bytes, q_bytes, quantized):
    """VMEM bytes of one step for ``hh`` KV heads, every block
    double-buffered: q and out; up to three (Tq*G, 1) f32 columns in
    (m, z, gate) and the m, z scratch; the f32 accumulator; P blocks of
    K and V (and their scale rows); one head's f32 scores and f32
    blocks."""
    col = _tile_bytes((hh, tq_g, 1), 4)
    rows = 2 * 2 * _tile_bytes((hh, tq_g, dh), q_bytes) + \
        (2 * 3 + 2) * col + _tile_bytes((hh, tq_g, dh), 4)
    kv = 2 * 2 * p * _tile_bytes((hh, bs, dh), kv_bytes)
    if quantized:
        kv += 2 * 2 * p * _tile_bytes((1, bs), 4)
    head = 6 * _tile_bytes((tq_g, p * bs), 4) + \
        2 * _tile_bytes((p * bs, dh), 4)
    return rows + kv + head


def _heads_per_step(hkv, **shape) -> int:
    """The largest divisor of Hkv whose step fits ``VMEM_BUDGET``."""
    fits = [h for h in range(1, hkv + 1) if hkv % h == 0
            and _working_set(h, **shape) <= VMEM_BUDGET]
    return max(fits, default=1)


def _live(cfg, rows, b, j):
    """Whether each of the P table entries of step ``j`` of row ``b`` is
    live: inside the row's bound and allocated."""
    tbl, _, bound = rows
    w, p = cfg["n_w"], cfg["p"]
    return [(j * p + i < bound[b]) &
            (tbl[b * w + jnp.minimum(j * p + i, w - 1)] >= 0)
            for i in range(p)]


def _mask(cfg, rows, b, j, live):
    """(Tq*G, P*BS) validity of the scores of step ``j`` of row ``b``: live
    entries, causal and local-window masks over logical positions."""
    off = rows[1]
    bs, n_k = cfg["block_size"], cfg["p"] * cfg["block_size"]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, n_k), 1)
    ok = jnp.zeros((1, n_k), jnp.bool_)
    for i, lv in enumerate(live):
        ok |= (col >= i * bs) & (col < (i + 1) * bs) & lv
    shape = (cfg["tq_g"], n_k)
    mask = jnp.broadcast_to(ok, shape)
    # query row r serves head-group lane r % G of query token r // G
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    if cfg["group"] > 1:
        row = row // cfg["group"]
    q_pos = off[b] + row
    k_pos = j * n_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    if cfg["causal"]:
        mask &= k_pos <= q_pos
    if cfg["window"] is not None:
        mask &= k_pos > q_pos - cfg["window"]
    return mask


def _step(cfg, rows, init, compute, finish):
    """The body every kernel entry shares: ``init`` at a row's first grid
    step, ``compute(mask)`` in each step inside the row's bound, and
    ``finish`` at its last grid step, working or not."""
    b, j = pl.program_id(0), pl.program_id(2)
    n = (rows[2][b] + cfg["p"] - 1) // cfg["p"]

    @pl.when(j == 0)
    def _():
        init()

    @pl.when(j < n)
    def _():
        compute(_mask(cfg, rows, b, j, _live(cfg, rows, b, j)))

    @pl.when(j == cfg["n_j"] - 1)
    def _():
        finish()


def _scale_row(refs):
    """(1, P*BS) per-key-slot scales of the step's P blocks, one lane per
    key, from their (1, 1, BS) rows; None for a float pool."""
    if refs is None:
        return None
    return jnp.concatenate([r[0] for r in refs], axis=1)


def _blocks(refs, h):
    """(P*BS, Dh) f32 keys (or values, int8 ones undequantized) of head
    ``h`` from the step's P pool blocks."""
    return jnp.concatenate([r[0, h].astype(jnp.float32) for r in refs],
                           axis=0)


def _scores(q_ref, k, ks, mask, h, cfg):
    """(Tq*G, P*BS) masked scores of head ``h``; an int8 pool's per-slot
    scale ``ks`` scales each key's score column."""
    q = q_ref[0, h].astype(jnp.float32)               # (Tq*G, Dh)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * cfg["scale"]
    if ks is not None:
        s = s * ks
    if cfg["softcap"] is not None:
        s = cfg["softcap"] * jnp.tanh(s / cfg["softcap"])
    return jnp.where(mask, s, NEG_INF)


def _pv(p, v, vs):
    """(Tq*G, Dh) = p @ V; an int8 pool's per-slot scale ``vs`` scales each
    key's probability column."""
    if vs is not None:
        p = p * vs
    return jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _heads(cfg, body):
    """``body(h)`` for each KV head of the step, in a loop (one head's
    scores live at a time)."""
    def each(h, carry):
        body(h)
        return carry

    jax.lax.fori_loop(0, cfg["heads"], each, 0)


def _vanilla_kernel(rows, q_ref, k_refs, v_refs, ks_refs, vs_refs, gate_ref,
                    o_ref, m_scr, z_scr, acc_scr, *, cfg):
    def init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        z_scr[...] = jnp.zeros_like(z_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def compute(mask):
        ks, vs = _scale_row(ks_refs), _scale_row(vs_refs)

        def head(h):
            s = _scores(q_ref, _blocks(k_refs, h), ks, mask, h, cfg)
            m_prev = m_scr[h]                         # (Tq*G, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            z_scr[h] = z_scr[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[h] = acc_scr[h] * corr + \
                _pv(p, _blocks(v_refs, h), vs)
            m_scr[h] = m_new

        _heads(cfg, head)

    def finish():
        out = acc_scr[...] / jnp.maximum(z_scr[...], 1e-30)
        if gate_ref is not None:
            out = out * gate_ref[0]
        o_ref[0] = out.astype(o_ref.dtype)

    _step(cfg, rows, init, compute, finish)


def _mz_kernel(rows, q_ref, k_refs, ks_refs, m_ref, z_ref, m_scr, z_scr, *,
               cfg):
    def init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        z_scr[...] = jnp.zeros_like(z_scr)

    def compute(mask):
        ks = _scale_row(ks_refs)

        def head(h):
            s = _scores(q_ref, _blocks(k_refs, h), ks, mask, h, cfg)
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            z_scr[h] = z_scr[h] * jnp.exp(m_prev - m_new) + \
                jnp.sum(p, axis=-1, keepdims=True)
            m_scr[h] = m_new

        _heads(cfg, head)

    def finish():
        m_ref[0] = m_scr[...]
        z_ref[0] = z_scr[...]

    _step(cfg, rows, init, compute, finish)


def _av_kernel(rows, q_ref, k_refs, v_refs, ks_refs, vs_refs, m_ref, z_ref,
               gate_ref, o_ref, acc_scr, *, cfg):
    def init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def compute(mask):
        ks, vs = _scale_row(ks_refs), _scale_row(vs_refs)

        def head(h):
            s = _scores(q_ref, _blocks(k_refs, h), ks, mask, h, cfg)
            p = jnp.exp(s - m_ref[0, h]) / jnp.maximum(z_ref[0, h], 1e-30)
            p = jnp.clip((cfg["zeta"] - cfg["gamma"]) * p + cfg["gamma"],
                         0.0, 1.0)
            p = jnp.where(mask, p, 0.0)
            acc_scr[h] = acc_scr[h] + _pv(p, _blocks(v_refs, h), vs)

        _heads(cfg, head)

    def finish():
        out = acc_scr[...]
        if gate_ref is not None:
            out = out * gate_ref[0]
        o_ref[0] = out.astype(o_ref.dtype)

    _step(cfg, rows, init, compute, finish)


# jitted so that a program which calls the kernel once per layer traces and
# lowers it once: the Mosaic lowering of a call costs ~0.45 s on the host
@functools.partial(jax.jit, static_argnames=(
    "group", "causal", "window", "softcap", "gamma", "zeta", "interpret"))
def paged_flash_attention(
    q: jax.Array,            # (B, Hkv, Tq*G, Dh) — head-grouped queries
    k_pool: jax.Array,       # (NB, Hkv, BS, Dh) — global block pool
    v_pool: jax.Array,
    block_table: jax.Array,  # (B, W) int32 physical block ids, -1 = unalloc
    q_off: jax.Array,        # (B,) int32 logical position of query row 0
    gate_pi: Optional[jax.Array] = None,    # (B, Hkv, Tq*G, 1)
    *,
    group: int = 1,          # G = Hq // Hkv (query rows per logical token)
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    gamma: float = 0.0,
    zeta: float = 1.0,
    k_scale: Optional[jax.Array] = None,    # (NB, 1, BS) f32 per-slot scales
    v_scale: Optional[jax.Array] = None,
    live_widths: Optional[jax.Array] = None,  # (B,) int32 live entries
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused paged attention; (gamma, zeta) = (0, 1) selects the single-pass
    vanilla path, anything else the two-pass clipped path.
    ``k_scale``/``v_scale`` mark the pools as int8: each step reads the
    scale rows of its blocks alongside them. ``live_widths`` bounds each
    row's visited entries (``live_bound``). ``interpret`` defaults to
    ``kernels.ops.default_interpret()``."""
    if interpret is None:
        interpret = default_interpret()
    b, hkv, tq_g, dh = q.shape
    nb, bs = k_pool.shape[0], k_pool.shape[2]
    w = block_table.shape[1]
    quantized = k_scale is not None
    p = blocks_per_step(bs, w)
    hh = _heads_per_step(hkv, tq_g=tq_g, dh=dh, bs=bs, p=p,
                         kv_bytes=k_pool.dtype.itemsize,
                         q_bytes=q.dtype.itemsize, quantized=quantized)
    n_j = -(-w // p)
    grid = (b, hkv // hh, n_j)
    cfg = dict(scale=dh ** -0.5, causal=causal, window=window,
               softcap=softcap, gamma=gamma, zeta=zeta, n_w=w, n_j=n_j, p=p,
               heads=hh, tq_g=tq_g, block_size=bs, group=group)

    off = q_off.astype(jnp.int32)
    bound = live_bound(off, tq_g // group, live_widths, block_size=bs,
                       width=w, causal=causal)
    rows = (block_table.astype(jnp.int32).reshape(-1), off, bound)

    # Operand i of a pool reads table entry j*P + i at step j — up to the
    # last step in which that entry is inside the row's bound; past it the
    # index stays put, so the pipeline fetches nothing. The clamp keeps an
    # unallocated (-1) entry a safe in-range read the mask hides.
    def block_id(i, bi, ji, tbl, bnd):
        last = jnp.maximum(bnd[bi] - 1 - i, 0) // p
        e = jnp.minimum(ji, last) * p + i
        return jnp.clip(tbl[bi * w + e], 0, nb - 1)

    def kv_spec(i: int):
        return pl.BlockSpec((1, hh, bs, dh), lambda bi, gi, ji, tbl, _o, bnd:
                            (block_id(i, bi, ji, tbl, bnd), gi, 0, 0))

    def sc_spec(i: int):
        return pl.BlockSpec((1, 1, bs), lambda bi, gi, ji, tbl, _o, bnd:
                            (block_id(i, bi, ji, tbl, bnd), 0, 0))

    def row_index(bi, gi, ji, *_):
        return (bi, gi, 0, 0)

    q_spec = pl.BlockSpec((1, hh, tq_g, dh), row_index)
    col_spec = pl.BlockSpec((1, hh, tq_g, 1), row_index)
    col_shape = jax.ShapeDtypeStruct((b, hkv, tq_g, 1), jnp.float32)
    out_shape = jax.ShapeDtypeStruct((b, hkv, tq_g, dh), q.dtype)
    col_scr = pltpu.VMEM((hh, tq_g, 1), jnp.float32)
    acc_scr = pltpu.VMEM((hh, tq_g, dh), jnp.float32)
    has_gate = gate_pi is not None
    if quantized:
        k_scale = k_scale.astype(jnp.float32)
        v_scale = v_scale.astype(jnp.float32)
    if has_gate:
        gate_pi = gate_pi.astype(jnp.float32)

    def pools(with_v):
        """[(array, spec)] of the P blocks of each pool a pass reads, then
        of each scale array."""
        arrays = [k_pool] + ([v_pool] if with_v else [])
        specs = [(a, kv_spec(i)) for a in arrays for i in range(p)]
        if quantized:
            scales = [k_scale] + ([v_scale] if with_v else [])
            specs += [(a, sc_spec(i)) for a in scales for i in range(p)]
        return specs

    # stable kernel names: the compiled tick's tpu_custom_call instructions
    # (and trace events) are named after them
    def call(name, kern, with_v, extra, out_specs, out_shapes, scratch):
        """``kern(rows, q_ref, groups, extra refs, out refs, scratch
        refs)``: ``groups`` holds the P refs of K, V, K scale, V scale in
        that order (None for the ones the pass does not read)."""
        inputs = [(q, q_spec)] + pools(with_v) + extra
        present = [True, with_v, quantized, quantized and with_v]
        n_flat = p * sum(present)
        n_out = len(out_shapes)

        def entry(*refs):
            rws, q_ref, refs = refs[:3], refs[3], refs[4:]
            chunks = iter(refs[k:k + p] for k in range(0, n_flat, p))
            groups = [list(next(chunks)) if pr else None for pr in present]
            ins, outs = refs[n_flat:n_flat + len(extra)], \
                refs[n_flat + len(extra):]
            kern(rws, q_ref, groups, ins, outs[:n_out], outs[n_out:])

        return pl.pallas_call(
            entry,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(rows),
                grid=grid,
                in_specs=[s for _, s in inputs],
                out_specs=out_specs,
                scratch_shapes=scratch,
            ),
            out_shape=out_shapes if n_out > 1 else out_shapes[0],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",) * 3,
                vmem_limit_bytes=VMEM_LIMIT),
            interpret=interpret,
            name=name,
        )(*rows, *[a for a, _ in inputs])

    gate_in = [(gate_pi, col_spec)] if has_gate else []

    if gamma == 0.0 and zeta == 1.0:
        def vanilla(rws, q_ref, groups, ins, outs, own):
            _vanilla_kernel(rws, q_ref, *groups,
                            ins[0] if has_gate else None, outs[0], *own,
                            cfg=cfg)

        return call("paged_attention", vanilla, True, gate_in, q_spec,
                    [out_shape], [col_scr, col_scr, acc_scr])

    # ---- clipped softmax: 2 streaming passes over the block table ----
    def mz(rws, q_ref, groups, ins, outs, own):
        _mz_kernel(rws, q_ref, groups[0], groups[2], *outs, *own, cfg=cfg)

    m, z = call("paged_attention_mz", mz, False, [], [col_spec, col_spec],
                [col_shape, col_shape], [col_scr, col_scr])

    def av(rws, q_ref, groups, ins, outs, own):
        _av_kernel(rws, q_ref, *groups, ins[0], ins[1],
                   ins[2] if has_gate else None, outs[0], *own, cfg=cfg)

    return call("paged_attention_av", av, True,
                [(m, col_spec), (z, col_spec)] + gate_in, q_spec,
                [out_shape], [acc_scr])


def paged_mha(
    q: jax.Array,            # (B, Tq, Hq, Dh) — model layout
    k_pool: jax.Array,       # (NB, Hkv, BS, Dh)
    v_pool: jax.Array,
    block_table: jax.Array,  # (B, W)
    q_offset=0,              # scalar or per-row (B,) int32
    gate_pi: Optional[jax.Array] = None,    # (B, Tq, Hq)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    gamma: float = 0.0,
    zeta: float = 1.0,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    live_widths: Optional[jax.Array] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Model-layout adapter: head-group the queries (all G query heads of a
    KV head share one pool-block read) and invoke the kernel. Returns
    (B, Tq, Hq, Dh) like ``dense_attention``."""
    b, tq, hq, dh = q.shape
    hkv = k_pool.shape[1]
    g = hq // hkv
    qf = q.reshape(b, tq, hkv, g, dh).transpose(0, 2, 1, 3, 4) \
        .reshape(b, hkv, tq * g, dh)
    gf = None
    if gate_pi is not None:
        gf = gate_pi.reshape(b, tq, hkv, g).transpose(0, 2, 1, 3) \
            .reshape(b, hkv, tq * g, 1)
    off = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (b,))
    if live_widths is not None:
        live_widths = jnp.asarray(live_widths, jnp.int32)
    out = paged_flash_attention(
        qf, k_pool, v_pool, block_table, off, gf, group=g, causal=causal,
        window=window, softcap=softcap, gamma=gamma, zeta=zeta,
        k_scale=k_scale, v_scale=v_scale, live_widths=live_widths,
        interpret=interpret)
    return out.reshape(b, hkv, tq, g, dh).transpose(0, 2, 1, 3, 4) \
        .reshape(b, tq, hq, dh)
