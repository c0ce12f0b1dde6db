"""Pallas TPU paged-attention decode kernel: in-place block-pool reads.

TPU twin of ``repro.core.attention.paged_attention``'s gather path,
specialized for serving ticks (decode Tq = 1, speculative Tq = k+1, and
prefill chunks) over the paged KV cache (vLLM/PagedAttention pattern).
The gather path materializes every row's virtual KV sequence — a
(B, W*block_size, Hkv, Dh) tensor per layer per tick — before attending;
this kernel never does. K/V stay in the global pools
``(num_blocks, Hkv, block_size, Dh)`` and each grid step DMAs ONE
(block_size, Dh) head tile of one physical pool block straight into VMEM,
addressed through a scalar-prefetched per-row block table
(``pltpu.PrefetchScalarGridSpec``): the table and per-row ``q_offset``
vector land in SMEM before the grid runs, so the k/v BlockSpec index_map
can read ``table[b, w]`` to pick the pool block for logical entry ``w``.
No gather, no virtual sequence, per-tick HBM traffic proportional to
blocks actually visited.

Layout: every BlockSpec's last two block dimensions equal the array's
(the TPU's (8, 128)-or-full tiling rule), which is why the pool is
head-major: one step reads the whole ``(block_size, Dh)`` tile of head
``h``. The per-slot int8 scales are ``(num_blocks, 1, block_size)`` — one
lane-major row per block — and the per-query m/z/gate vectors are
``(B, Hkv, Tq*G, 1)`` columns, so every operand is a 2-D tile.

Grid: ``(B, Hkv, W)`` with the block-table dimension innermost and
sequential, so the f32 VMEM scratch carries the online-softmax state
across a row's blocks. All ``G = Hq/Hkv`` query heads of one KV head are
processed together as a (Tq*G, Dh) tile — one pool block read serves the
whole query group.

Semantics match the gather oracle:

  * causal + local-window masks over *logical* positions built from the
    prefetched per-row ``q_offset`` (scalar or (B,) vector);
  * unallocated table entries (id < 0) contribute nothing (the index_map
    clamps the pool read to a safe block, the kernel masks it out);
  * logit soft-capping;
  * vanilla softmax = single online pass; the paper's clipped softmax =
    the same TWO streaming passes as ``kernels/flash_attention.py``
    (pass 1 emits the per-query (m, Z), pass 2 re-streams the blocks and
    accumulates clip((zeta-gamma)·p + gamma, 0, 1) @ V). ``gamma`` is the
    caller's resolved value (``AttentionConfig.gamma``);
  * the per-head gate ``pi`` multiplies the output tile in the epilogue;
  * int8 pools (``init_paged_cache(kv_int8=True)``): a block's scale row
    rides the SAME table-driven index_map as its pool block. A scale is
    per key slot, so it scales a score column (K) or a probability column
    (V): ``(q @ k_q^T) * ks`` and ``(p * vs) @ v_q``. Stale scales in
    recycled blocks are masked exactly like stale KV.

Accumulation is f32 blockwise streaming, so results match the gather
oracle to f32 round-off of the differing reduction order, not bitwise.

Oracle: ``paged_attention(..., backend="gather")``; swept over dtypes, GQA
ratios, masks, (gamma, zeta), ragged per-row positions and partial tail
blocks in tests/test_paged_kernel.py (interpret mode on CPU), and compiled
for a v5e at OPT-125m widths in tests/test_tpu_compile.py.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import default_interpret

NEG_INF = -1e30


def _scores(tbl_ref, off_ref, q_ref, k_ref, ks_ref, *, cfg):
    """(Tq*G, BS) masked scores of one (row, kv-head, table-entry) step."""
    b, w = pl.program_id(0), pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32)               # (Tq*G, Dh)
    k = k_ref[0, 0].astype(jnp.float32)               # (BS, Dh)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * cfg["scale"]
    if ks_ref is not None:                            # int8 pool: per-slot
        s = s * ks_ref[0]                             # scale of each key
    if cfg["softcap"] is not None:
        s = cfg["softcap"] * jnp.tanh(s / cfg["softcap"])
    shape = (cfg["tq_g"], cfg["block_size"])
    # query row r serves head-group lane r % G of query token r // G
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    if cfg["group"] > 1:
        row = row // cfg["group"]
    q_pos = off_ref[b] + row
    k_pos = w * cfg["block_size"] + jax.lax.broadcasted_iota(
        jnp.int32, shape, 1)
    # unallocated entry: every key of the block is masked
    mask = k_pos >= jnp.where(tbl_ref[b, w] >= 0, 0, jnp.iinfo(jnp.int32).max)
    if cfg["causal"]:
        mask &= k_pos <= q_pos
    if cfg["window"] is not None:
        mask &= k_pos > q_pos - cfg["window"]
    return jnp.where(mask, s, NEG_INF), mask


def _pv(p, v_ref, vs_ref):
    """(Tq*G, Dh) = p @ V of one pool block, dequantized if int8."""
    if vs_ref is not None:
        p = p * vs_ref[0]                             # per-slot V scale
    return jax.lax.dot_general(
        p, v_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _vanilla_kernel(tbl_ref, off_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                    gate_ref, o_ref, m_scr, z_scr, acc_scr, *, cfg):
    w = pl.program_id(2)

    @pl.when(w == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        z_scr[...] = jnp.zeros_like(z_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    s, mask = _scores(tbl_ref, off_ref, q_ref, k_ref, ks_ref, cfg=cfg)
    m_prev = m_scr[...]                               # (Tq*G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    z_scr[...] = z_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + _pv(p, v_ref, vs_ref)
    m_scr[...] = m_new

    @pl.when(w == cfg["n_w"] - 1)
    def _():
        out = acc_scr[...] / jnp.maximum(z_scr[...], 1e-30)
        if gate_ref is not None:
            out = out * gate_ref[0, 0]
        o_ref[0, 0] = out.astype(o_ref.dtype)


def _mz_kernel(tbl_ref, off_ref, q_ref, k_ref, ks_ref, m_ref, z_ref,
               m_scr, z_scr, *, cfg):
    w = pl.program_id(2)

    @pl.when(w == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        z_scr[...] = jnp.zeros_like(z_scr)

    s, mask = _scores(tbl_ref, off_ref, q_ref, k_ref, ks_ref, cfg=cfg)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    z_scr[...] = z_scr[...] * jnp.exp(m_prev - m_new) + \
        jnp.sum(p, axis=-1, keepdims=True)
    m_scr[...] = m_new

    @pl.when(w == cfg["n_w"] - 1)
    def _():
        m_ref[0, 0] = m_scr[...]
        z_ref[0, 0] = z_scr[...]


def _av_kernel(tbl_ref, off_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
               m_ref, z_ref, gate_ref, o_ref, acc_scr, *, cfg):
    w = pl.program_id(2)

    @pl.when(w == 0)
    def _():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    s, mask = _scores(tbl_ref, off_ref, q_ref, k_ref, ks_ref, cfg=cfg)
    p = jnp.exp(s - m_ref[0, 0]) / jnp.maximum(z_ref[0, 0], 1e-30)
    p = jnp.clip((cfg["zeta"] - cfg["gamma"]) * p + cfg["gamma"], 0.0, 1.0)
    p = jnp.where(mask, p, 0.0)
    acc_scr[...] += _pv(p, v_ref, vs_ref)

    @pl.when(w == cfg["n_w"] - 1)
    def _():
        out = acc_scr[...]
        if gate_ref is not None:
            out = out * gate_ref[0, 0]
        o_ref[0, 0] = out.astype(o_ref.dtype)


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
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused paged attention; (gamma, zeta) = (0, 1) selects the single-pass
    vanilla path, anything else the two-pass clipped path.
    ``k_scale``/``v_scale`` mark the pools as int8: each grid step DMAs the
    visited block's scale row alongside it. ``interpret`` defaults to
    ``kernels.ops.default_interpret()``."""
    if interpret is None:
        interpret = default_interpret()
    b, hkv, tq_g, dh = q.shape
    nb, bs = k_pool.shape[0], k_pool.shape[2]
    w = block_table.shape[1]
    grid = (b, hkv, w)
    cfg = dict(scale=dh ** -0.5, causal=causal, window=window,
               softcap=softcap, gamma=gamma, zeta=zeta, n_w=w,
               tq_g=tq_g, block_size=bs, group=group)

    table = block_table.astype(jnp.int32)
    off = q_off.astype(jnp.int32)

    # the index_map receives (grid ids..., scalar-prefetch refs...); the
    # clamp keeps unallocated (-1) entries a safe in-range DMA — the kernel
    # masks their contribution out via tbl_ref[b, w] >= 0
    def kv_index(bi, hi, wi, tbl, _off):
        return (jnp.clip(tbl[bi, wi], 0, nb - 1), hi, 0, 0)

    # int8 pools: the scale row of the visited block rides the same
    # table-driven indirection — one (1, BS) f32 row per block DMA
    def sc_index(bi, hi, wi, tbl, _off):
        return (jnp.clip(tbl[bi, wi], 0, nb - 1), 0, 0)

    def row_index(bi, hi, wi, tbl, _off):
        return (bi, hi, 0, 0)

    q_spec = pl.BlockSpec((1, 1, tq_g, dh), row_index)
    kv_spec = pl.BlockSpec((1, 1, bs, dh), kv_index)
    sc_spec = pl.BlockSpec((1, 1, bs), sc_index)
    o_spec = pl.BlockSpec((1, 1, tq_g, dh), row_index)
    col_spec = pl.BlockSpec((1, 1, tq_g, 1), row_index)
    col_shape = jax.ShapeDtypeStruct((b, hkv, tq_g, 1), jnp.float32)
    col_scr = pltpu.VMEM((tq_g, 1), jnp.float32)
    acc_scr = pltpu.VMEM((tq_g, dh), jnp.float32)
    has_gate = gate_pi is not None
    quantized = k_scale is not None
    if quantized:
        k_scale = k_scale.astype(jnp.float32)
        v_scale = v_scale.astype(jnp.float32)
    if has_gate:
        gate_pi = gate_pi.astype(jnp.float32)

    # stable kernel names: the compiled tick's tpu_custom_call instructions
    # (and trace events) are named after them
    def call(name, kern, in_specs, args, out_specs, out_shape, scratch):
        return pl.pallas_call(
            kern,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=grid,
                in_specs=in_specs,
                out_specs=out_specs,
                scratch_shapes=scratch,
            ),
            out_shape=out_shape,
            interpret=interpret,
            name=name,
        )(table, off, *args)

    # optional inputs (scale rows, gate) are appended positionally; each
    # entry adapter peels the refs present for this configuration and calls
    # the kernel with None for the absent ones (quantized/has_gate are
    # trace-time constants, so the kernels specialize cleanly)
    if gamma == 0.0 and zeta == 1.0:
        in_specs = [q_spec, kv_spec, kv_spec]
        args = [q, k_pool, v_pool]
        if quantized:
            in_specs += [sc_spec, sc_spec]
            args += [k_scale, v_scale]
        if has_gate:
            in_specs += [col_spec]
            args += [gate_pi]

        def vanilla_entry(t, of, *rest):
            it = iter(rest)
            qr, kr, vr = next(it), next(it), next(it)
            ks, vs = (next(it), next(it)) if quantized else (None, None)
            gr = next(it) if has_gate else None
            o, m, z, a = next(it), next(it), next(it), next(it)
            _vanilla_kernel(t, of, qr, kr, vr, ks, vs, gr, o, m, z, a,
                            cfg=cfg)

        return call(
            "paged_attention", vanilla_entry, in_specs, args, o_spec,
            jax.ShapeDtypeStruct((b, hkv, tq_g, dh), q.dtype),
            [col_scr, col_scr, acc_scr])

    # ---- clipped softmax: 2 streaming passes over the block table ----
    def mz_entry(t, of, *rest):
        it = iter(rest)
        qr, kr = next(it), next(it)
        ks = next(it) if quantized else None
        mr, zr, ms, zs = next(it), next(it), next(it), next(it)
        _mz_kernel(t, of, qr, kr, ks, mr, zr, ms, zs, cfg=cfg)

    m, z = call(
        "paged_attention_mz", mz_entry,
        [q_spec, kv_spec] + ([sc_spec] if quantized else []),
        [q, k_pool] + ([k_scale] if quantized else []),
        [col_spec, col_spec], [col_shape, col_shape], [col_scr, col_scr])

    in_specs = [q_spec, kv_spec, kv_spec]
    args = [q, k_pool, v_pool]
    if quantized:
        in_specs += [sc_spec, sc_spec]
        args += [k_scale, v_scale]
    in_specs += [col_spec, col_spec]
    args += [m, z]
    if has_gate:
        in_specs += [col_spec]
        args += [gate_pi]

    def av_entry(t, of, *rest):
        it = iter(rest)
        qr, kr, vr = next(it), next(it), next(it)
        ks, vs = (next(it), next(it)) if quantized else (None, None)
        mr, zr = next(it), next(it)
        gr = next(it) if has_gate else None
        o, a = next(it), next(it)
        _av_kernel(t, of, qr, kr, vr, ks, vs, mr, zr, gr, o, a, cfg=cfg)

    return call(
        "paged_attention_av", av_entry, in_specs, args, o_spec,
        jax.ShapeDtypeStruct((b, hkv, tq_g, dh), q.dtype), [acc_scr])


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
    out = paged_flash_attention(
        qf, k_pool, v_pool, block_table, off, gf, group=g, causal=causal,
        window=window, softcap=softcap, gamma=gamma, zeta=zeta,
        k_scale=k_scale, v_scale=v_scale, interpret=interpret)
    return out.reshape(b, hkv, tq, g, dh).transpose(0, 2, 1, 3, 4) \
        .reshape(b, tq, hq, dh)
