"""Composable transformer covering the whole assigned architecture pool.

One ``ModelConfig`` describes dense GQA transformers (deepseek/qwen/codeqwen),
gemma-2 (alternating local/global attention + logit soft-caps + sandwich
norms), MoE transformers (granite, qwen2-moe), the Griffin hybrid
(recurrentgemma), xLSTM stacks, encoder-only audio (hubert) and
VLM/text backbones (phi-3-vision) — plus the paper's own BERT/OPT/ViT-style
models. The paper's knobs (``softmax_cfg``, ``gate_cfg``) apply to every
softmax-attention block.

Layer-group execution: ``pattern`` lists the block kinds of one group (e.g.
("rec", "rec", "attn") for recurrentgemma); the model scans over
``n_layers // len(pattern)`` stacked groups (fast compile at 95 layers, the
MaxText trick) with an optional un-scanned tail for non-divisible depths.
``scan_layers=False`` python-unrolls — required for PTQ calibration where
every layer needs its own activation-range site.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.attention import (
    AttentionConfig,
    attention,
    dense_attention,
    paged_attention,
)
from repro.core.gating import GateConfig, gate_probs, init_gate
from repro.core.softmax import ClippedSoftmaxConfig, softcap
from repro.nn.layers import (
    apply_rope,
    embedding_apply,
    embedding_attend,
    embedding_init,
    linear_apply,
    linear_init,
    norm_apply,
    norm_init,
    positional_embedding_apply,
    positional_embedding_init,
    rmsnorm_apply,
    rmsnorm_init,
    rope_angles,
)
from repro.nn.mlp import mlp_apply, mlp_init
from repro.nn.moe import MoEConfig, moe_apply, moe_init
from repro.nn.module import Array, Params, split_keys, tree_slice, tree_stack
from repro.nn.recurrent import (
    RGLRUConfig,
    griffin_block_apply,
    griffin_block_init,
    griffin_init_state,
)
from repro.nn.xlstm import (
    XLSTMConfig,
    mlstm_block_apply,
    mlstm_block_init,
    slstm_block_apply,
    slstm_block_init,
    xlstm_init_state,
)
from repro.quant.kv_cache import kv_quant
from repro.quant.qconfig import NO_QUANT, QuantContext


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    d_head: Optional[int] = None

    # block pattern (one "group"); kinds: attn | local_attn | griffin | mlstm | slstm
    pattern: Tuple[str, ...] = ("attn",)

    # attention
    causal: bool = True
    window: Optional[int] = None                # for local_attn kind
    attn_logit_softcap: Optional[float] = None
    final_logit_softcap: Optional[float] = None
    qk_norm: bool = False
    pos: str = "rope"                           # rope | learned | none
    rope_theta: float = 10000.0
    max_seq_len: int = 131072
    attn_chunk_size: int = 1024

    # norms / residual
    norm: str = "rmsnorm"                       # rmsnorm | layernorm
    norm_position: str = "pre"                  # pre | post (BERT)
    post_block_norm: bool = False               # gemma-2 sandwich norms

    # mlp
    mlp_kind: str = "swiglu"                    # gelu | gelu_tanh | swiglu | none
    moe: Optional[MoEConfig] = None

    # paper knobs
    softmax_cfg: ClippedSoftmaxConfig = ClippedSoftmaxConfig()
    gate_cfg: GateConfig = GateConfig(kind="none")
    # the length the model is trained at: T of the clipped softmax's
    # gamma = -alpha / T on every path, training and serving alike
    train_seq_len: Optional[int] = None

    # paged-KV read path: "auto" (Pallas kernel where it runs compiled, XLA
    # gather elsewhere — kernels.ops.default_interpret) | "kernel" |
    # "gather" — see core.attention.paged_attention
    paged_backend: str = "auto"

    # embedding / io
    tie_embeddings: bool = True
    embed_scale: bool = False                   # gemma: * sqrt(d_model)
    input_kind: str = "tokens"                  # tokens | embeds | mixed
    frontend_dim: Optional[int] = None          # embeds input width (e.g. 512)
    n_prefix_embeds: int = 0                    # vlm: image-patch prefix length

    # sub-configs for non-attention mixers
    rglru: Optional[RGLRUConfig] = None
    xlstm: Optional[XLSTMConfig] = None

    # vocab padded to a multiple of this so the vocab dim shards over the
    # 'model' mesh axis (padded logits are masked to -inf before the loss)
    vocab_pad_to: int = 1

    # execution
    scan_layers: bool = True
    remat: bool = True
    remat_policy: str = "nothing"     # nothing | dots (save matmul outputs)
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32
    init_std: float = 0.02

    # ------------------------------------------------------------------
    @property
    def padded_vocab(self) -> int:
        m = self.vocab_pad_to
        return (self.vocab_size + m - 1) // m * m

    @property
    def head_dim(self) -> int:
        return self.d_head if self.d_head is not None else self.d_model // self.n_heads

    @property
    def n_groups(self) -> int:
        return self.n_layers // len(self.pattern)

    @property
    def tail_pattern(self) -> Tuple[str, ...]:
        return self.pattern[: self.n_layers % len(self.pattern)]

    def attn_cfg(self, kind: str) -> AttentionConfig:
        return AttentionConfig(
            n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads,
            d_head=self.head_dim,
            causal=self.causal,
            window=self.window if kind == "local_attn" else None,
            logit_softcap=self.attn_logit_softcap,
            softmax=self.softmax_cfg,
            chunk_size=self.attn_chunk_size,
            seq_len=self.train_seq_len,
        )


# ==========================================================================
# Decode positions
# ==========================================================================
def _positions(pos, t: int) -> Array:
    """Absolute positions of a length-``t`` block: (T,) for a scalar ``pos``,
    (B, T) when ``pos`` is a per-row (B,) vector (slot-pool decode)."""
    p = jnp.asarray(pos, jnp.int32)
    return p[..., None] + jnp.arange(t, dtype=jnp.int32)


def _row_select(active: Array, new, old):
    """Keep ``new`` state only for rows where ``active`` is True. Used for
    decode states without a positional write index (recurrent h, conv tail)
    where a masked scatter does not apply."""
    def sel(n, o):
        if n is None:
            return n
        m = active.reshape(active.shape[0], *([1] * (n.ndim - 1)))
        return jnp.where(m, n, o.astype(n.dtype))
    return jax.tree_util.tree_map(sel, new, old)


def _token_mask(active: Optional[Array], b: int, t: int) -> Optional[Array]:
    """Normalize the ``active`` argument to a per-token (B, T) bool mask.

    ``active`` may be a per-row (B,) mask (every token of a row shares its
    fate — the decode-tick contract) or already per-token (B, T) — the
    chunked-prefill contract, where row b contributes ``counts[b] <= T``
    real tokens and the tail of its block is padding whose cache writes must
    be dropped."""
    if active is None:
        return None
    act = jnp.asarray(active)
    if act.ndim == 1:
        act = act[:, None]
    return jnp.broadcast_to(act.astype(jnp.bool_), (b, t))


def _row_active(active: Optional[Array]) -> Optional[Array]:
    """Per-row (B,) reduction of ``active`` for states without a positional
    write index (recurrent h/conv/cell). A row participates if ANY of its
    tokens is live; ragged (partially live) rows are not representable for
    recurrent states — the scheduler feeds recurrent models uniform-length
    steps (see ``serving.scheduler``)."""
    if active is None or active.ndim == 1:
        return active
    return active.any(axis=1)


# ==========================================================================
# Block init / apply
# ==========================================================================
def _attn_block_init(key: Array, cfg: ModelConfig, kind: str) -> Params:
    d, hq, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = split_keys(key, 8)
    std = cfg.init_std
    bias = cfg.norm == "layernorm"  # BERT/OPT-style models use biases
    p: Params = {
        "ln1": norm_init(cfg.norm, d, cfg.param_dtype),
        "q": linear_init(ks[0], d, hq * dh, bias=bias, std=std, dtype=cfg.param_dtype),
        "k": linear_init(ks[1], d, hkv * dh, bias=bias, std=std, dtype=cfg.param_dtype),
        "v": linear_init(ks[2], d, hkv * dh, bias=bias, std=std, dtype=cfg.param_dtype),
        "o": linear_init(ks[3], hq * dh, d, bias=bias, std=std, dtype=cfg.param_dtype),
    }
    if cfg.qk_norm:
        p["qnorm"] = rmsnorm_init(dh, cfg.param_dtype)
        p["knorm"] = rmsnorm_init(dh, cfg.param_dtype)
    if cfg.gate_cfg.enabled:
        p["gate"] = init_gate(ks[4], cfg.gate_cfg, hq, dh, d, cfg.param_dtype)
    if cfg.mlp_kind != "none":
        p["ln2"] = norm_init(cfg.norm, d, cfg.param_dtype)
        if cfg.moe is not None:
            p["moe"] = moe_init(ks[5], d, cfg.moe, cfg.param_dtype)
        else:
            p["mlp"] = mlp_init(ks[5], d, cfg.d_ff, cfg.mlp_kind, cfg.param_dtype)
    if cfg.post_block_norm:
        p["post_ln1"] = norm_init(cfg.norm, d, cfg.param_dtype)
        if cfg.mlp_kind != "none":
            p["post_ln2"] = norm_init(cfg.norm, d, cfg.param_dtype)
    return p


def _block_init(key: Array, cfg: ModelConfig, kind: str) -> Params:
    if kind in ("attn", "local_attn"):
        return _attn_block_init(key, cfg, kind)
    if kind == "griffin":
        k1, k2, k3 = split_keys(key, 3)
        return {
            "ln1": norm_init(cfg.norm, cfg.d_model, cfg.param_dtype),
            "griffin": griffin_block_init(k1, cfg.d_model, cfg.rglru, cfg.param_dtype),
            "ln2": norm_init(cfg.norm, cfg.d_model, cfg.param_dtype),
            "mlp": mlp_init(k2, cfg.d_model, cfg.d_ff, cfg.mlp_kind, cfg.param_dtype),
        }
    if kind == "mlstm":
        return {
            "ln": norm_init(cfg.norm, cfg.d_model, cfg.param_dtype),
            "blk": mlstm_block_init(key, cfg.xlstm, cfg.param_dtype),
        }
    if kind == "slstm":
        return {
            "ln": norm_init(cfg.norm, cfg.d_model, cfg.param_dtype),
            "blk": slstm_block_init(key, cfg.xlstm, cfg.param_dtype),
        }
    raise ValueError(f"unknown block kind {kind!r}")


def _attn_block_apply(
    p: Params, x: Array, cfg: ModelConfig, kind: str,
    rope: Optional[Tuple[Array, Array]],
    cache: Optional[dict], pos,
    ctx: QuantContext, name: str,
    active: Optional[Array] = None,
    paged_live_width: Optional[int] = None,
    paged_live_widths: Optional[Array] = None,
) -> Tuple[Array, Optional[dict], Array, dict]:
    """Returns (x_out, new_cache, attn_layer_output, moe_aux); the attention
    layer output is the tensor whose outliers the paper measures."""
    b, t, d = x.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    acfg = cfg.attn_cfg(kind)

    h = norm_apply(cfg.norm, p["ln1"], x, ctx, name + "/ln1") \
        if cfg.norm_position == "pre" else x
    q = linear_apply(p["q"], h, ctx, name + "/q").reshape(b, t, hq, dh)
    k = linear_apply(p["k"], h, ctx, name + "/k").reshape(b, t, hkv, dh)
    v = linear_apply(p["v"], h, ctx, name + "/v").reshape(b, t, hkv, dh)
    if cfg.qk_norm:
        q = rmsnorm_apply(p["qnorm"], q, ctx=ctx, name=name + "/qnorm")
        k = rmsnorm_apply(p["knorm"], k, ctx=ctx, name=name + "/knorm")
    if rope is not None:
        cos, sin = rope
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

    explicit_mask = None
    paged_table = None
    paged_scales = None
    if cache is not None:
        # align fresh q/k/v sharding with the d_head-sharded KV cache —
        # otherwise GSPMD falls back to "involuntary full rematerialization"
        # (replicate-then-reshard) on every decode step
        from repro.distributed.sharding import maybe_constrain
        q = maybe_constrain(q, "dp", None, None, "tp")
        k = maybe_constrain(k, "dp", None, None, "tp")
        v = maybe_constrain(v, "dp", None, None, "tp")
        cache_len = cache["k"].shape[1]
        is_ring = "pos_ids" in cache
        is_paged = "block_table" in cache
        per_row = jnp.ndim(pos) >= 1      # per-slot positions (decode engine)
        act_tok = _token_mask(active, b, t)   # (B, T) or None
        ring_read = None
        if is_paged:
            # Paged pool (num_blocks, Hkv, block_size, Dh): every write is
            # routed through block_table[row, pos // block_size] indirection.
            # Unallocated targets (table entry -1) and inactive tokens are
            # redirected out of bounds and dropped, the same masked-scatter
            # convention as the dense per-row path below.
            # Speculative decoding leans on a second property of this
            # scatter: a REJECTED draft token's write (an active token the
            # scheduler later declines to bank) is harmless, because reads
            # mask keys by logical position (> q is invisible) and the
            # row's next writes at those same (phys, slot) targets replace
            # the entry — with identical bits, since stored KV (incl. the
            # fused int8 quantize below) is a pure function of
            # (token value, logical position). Ring and recurrent caches
            # lack this replay property, so the scheduler refuses spec
            # there.
            with jax.named_scope("attn/kv_write"):
                nb, bs = cache["k"].shape[0], cache["k"].shape[2]
                table = cache["block_table"]                         # (B, W)
                tpos = jnp.broadcast_to(_positions(pos, t), (b, t))  # logical
                phys = jnp.take_along_axis(table, tpos // bs, axis=1,
                                           mode="fill", fill_value=-1)
                if act_tok is not None:
                    phys = jnp.where(act_tok, phys, -1)
                phys = jnp.where(phys < 0, nb, phys)    # out of bounds -> dropped
                slot = tpos % bs
                # pool[phys, :, slot] is (B, T, Hkv, Dh): numpy's rule puts the
                # broadcast index dims first when a slice separates them
                if "k_scale" in cache:
                    # int8 pool: quantization fused into the same masked scatter.
                    # Each token is quantized exactly ONCE from its fp value —
                    # its int8 code + per-slot scale land together, so stored
                    # bits are a pure function of (value, logical position) and
                    # serving stays bitwise invariant to chunking/slots/resume
                    # (see quant.kv_cache for why not a scalar per-block scale).
                    kq, ks = kv_quant(k)
                    vq, vs = kv_quant(v)
                    k_cache = cache["k"].at[phys, :, slot].set(kq, mode="drop")
                    v_cache = cache["v"].at[phys, :, slot].set(vq, mode="drop")
                    new_cache = {
                        "k": k_cache, "v": v_cache,
                        "k_scale": cache["k_scale"].at[phys, 0, slot].set(
                            ks, mode="drop"),
                        "v_scale": cache["v_scale"].at[phys, 0, slot].set(
                            vs, mode="drop"),
                        "block_table": table,
                    }
                    paged_scales = (new_cache["k_scale"], new_cache["v_scale"])
                else:
                    k_cache = cache["k"].at[phys, :, slot].set(
                        k.astype(cache["k"].dtype), mode="drop")
                    v_cache = cache["v"].at[phys, :, slot].set(
                        v.astype(cache["v"].dtype), mode="drop")
                    new_cache = {"k": k_cache, "v": v_cache, "block_table": table}
            paged_table = table
        elif per_row:
            # Masked per-token scatter: row b writes token j of its block at
            # position pos[b] + j; padding tokens (act_tok False) and dead
            # rows are redirected out of bounds and dropped — no write, no
            # double-buffer restore needed. A chunk (t > 1) must satisfy
            # t <= ring length for local_attn layers so its own writes do
            # not collide inside the ring (the scheduler caps chunks at the
            # window).
            tpos = _positions(pos, t)                                # (B, T)
            widx = tpos % cache_len if is_ring else tpos
            if act_tok is not None:
                widx = jnp.where(act_tok, widx, cache_len)
            bidx = jnp.arange(b)[:, None]
            k_cache = cache["k"].at[bidx, widx].set(
                k.astype(cache["k"].dtype), mode="drop")
            v_cache = cache["v"].at[bidx, widx].set(
                v.astype(cache["v"].dtype), mode="drop")
            new_cache = {"k": k_cache, "v": v_cache}
            if is_ring:
                pos_ids = cache["pos_ids"].at[bidx, widx].set(tpos, mode="drop")
                new_cache["pos_ids"] = pos_ids
                q_pos = tpos[:, :, None]                             # (B, T, 1)
                if t == 1:
                    # decode: the single fresh token never evicts in-window
                    # history, so attend over the updated ring directly
                    kp = pos_ids[:, None, :]                         # (B, 1, L)
                else:
                    # chunked prefill: a multi-token ring write can evict
                    # history that EARLIER queries of the same chunk still
                    # need (slot (pos+j) % L holds position pos+j-L, inside
                    # the window of queries j' < j). Read the PRE-write ring
                    # plus the fresh chunk as separate KV entries instead:
                    # the position-id mask picks exactly the in-window,
                    # causal, live subset of both segments, and padding
                    # tokens of the fresh segment are tagged -1.
                    fpos = tpos if act_tok is None else \
                        jnp.where(act_tok, tpos, -1)
                    kp = jnp.concatenate([cache["pos_ids"], fpos],
                                         axis=1)[:, None, :]   # (B, 1, L+T)
                    ring_read = (
                        jnp.concatenate(
                            [cache["k"], k.astype(cache["k"].dtype)], axis=1),
                        jnp.concatenate(
                            [cache["v"], v.astype(cache["v"].dtype)], axis=1),
                    )
                explicit_mask = (kp >= 0) & (kp <= q_pos) & \
                    (kp > q_pos - cfg.window)
                acfg = dataclasses.replace(acfg, causal=False, window=None)
        elif is_ring:
            # ring buffer holding the last `window` tokens (decode, t == 1)
            slot = pos % cache_len
            k_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
            pos_ids = jax.lax.dynamic_update_slice_in_dim(
                cache["pos_ids"],
                jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32) + pos, (b, t)),
                slot, axis=1)
            new_cache = {"k": k_cache, "v": v_cache, "pos_ids": pos_ids}
            q_pos = (pos + jnp.arange(t))[None, :, None]             # (1, T, 1)
            kp = pos_ids[:, None, :]                                 # (B, 1, L)
            explicit_mask = (kp >= 0) & (kp <= q_pos) & (kp > q_pos - cfg.window)
            acfg = dataclasses.replace(acfg, causal=False, window=None)
        else:
            k_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), pos, axis=1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), pos, axis=1)
            new_cache = {"k": k_cache, "v": v_cache}
        k_all, v_all = ring_read if ring_read is not None else (k_cache, v_cache)
        q_offset = pos
    else:
        new_cache = None
        k_all, v_all = k, v
        q_offset = 0

    gate_pi = None
    if cfg.gate_cfg.enabled:
        # per-head view of the attention input (paper Sec 4.2); when
        # n_heads*d_head != d_model (gemma2) the per-head query projection
        # is the per-head view instead.
        if hq * dh == d:
            x_heads = h.reshape(b, t, hq, dh)
        else:
            x_heads = q
        gate_pi = gate_probs(p["gate"], cfg.gate_cfg, x_heads, h)

    if paged_table is not None:
        with jax.named_scope("attn/paged"):
            k_scale, v_scale = paged_scales if paged_scales is not None else (None, None)
            attn_out = paged_attention(q, k_all, v_all, paged_table, acfg,
                                       q_offset=q_offset, gate_pi=gate_pi,
                                       live_width=paged_live_width,
                                       live_widths=paged_live_widths,
                                       k_scale=k_scale, v_scale=v_scale,
                                       backend=cfg.paged_backend)
    elif explicit_mask is not None:
        attn_out = dense_attention(q, k_all, v_all, acfg, mask=explicit_mask,
                                   q_offset=q_offset, gate_pi=gate_pi)
    else:
        attn_out = attention(q, k_all, v_all, acfg, q_offset=q_offset, gate_pi=gate_pi)
    attn_out = ctx.act(name + "/attn.out", attn_out.reshape(b, t, hq * dh))
    y = linear_apply(p["o"], attn_out, ctx, name + "/o")
    if cfg.post_block_norm:
        y = norm_apply(cfg.norm, p["post_ln1"], y, ctx, name + "/post_ln1")
    x = x + y
    if cfg.norm_position == "post":
        x = norm_apply(cfg.norm, p["ln1"], x, ctx, name + "/ln1")
    attn_layer_out = x  # residual-stream value after attention (paper metric)

    moe_aux = {"load_balance": jnp.zeros((), jnp.float32),
               "router_z": jnp.zeros((), jnp.float32)}
    with jax.named_scope("mlp"):
        if cfg.mlp_kind != "none":
            h2 = norm_apply(cfg.norm, p["ln2"], x, ctx, name + "/ln2") \
                if cfg.norm_position == "pre" else x
            if cfg.moe is not None:
                # inactive decode rows must not claim expert capacity: their
                # tokens would displace live rows' tokens in the dropping
                # dispatch (slot-major priority), silently changing live outputs
                y2, moe_aux = moe_apply(p["moe"], h2, cfg.moe, ctx, name + "/moe",
                                        active=active)
            else:
                y2 = mlp_apply(p["mlp"], h2, cfg.mlp_kind, ctx, name + "/mlp")
            if cfg.post_block_norm:
                y2 = norm_apply(cfg.norm, p["post_ln2"], y2, ctx, name + "/post_ln2")
            x = x + y2
            if cfg.norm_position == "post":
                x = norm_apply(cfg.norm, p["ln2"], x, ctx, name + "/ln2")
    return x, new_cache, attn_layer_out, moe_aux


def _zero_aux():
    return {"load_balance": jnp.zeros((), jnp.float32),
            "router_z": jnp.zeros((), jnp.float32)}


def _block_apply(
    p: Params, x: Array, cfg: ModelConfig, kind: str,
    rope, cache, pos, ctx: QuantContext, name: str,
    active: Optional[Array] = None,
    paged_live_width: Optional[int] = None,
    paged_live_widths: Optional[Array] = None,
) -> Tuple[Array, Optional[dict], Array, dict]:
    if kind in ("attn", "local_attn"):
        return _attn_block_apply(p, x, cfg, kind, rope, cache, pos, ctx, name,
                                 active=active,
                                 paged_live_width=paged_live_width,
                                 paged_live_widths=paged_live_widths)
    if kind == "griffin":
        h = norm_apply(cfg.norm, p["ln1"], x, ctx, name + "/ln1")
        y, new_state = griffin_block_apply(p["griffin"], h, cfg.rglru, cache, ctx, name + "/griffin")
        if active is not None and cache is not None:
            new_state = _row_select(_row_active(active), new_state, cache)
        x = x + y
        mix_out = x
        h2 = norm_apply(cfg.norm, p["ln2"], x, ctx, name + "/ln2")
        x = x + mlp_apply(p["mlp"], h2, cfg.mlp_kind, ctx, name + "/mlp")
        return x, new_state, mix_out, _zero_aux()
    if kind in ("mlstm", "slstm"):
        h = norm_apply(cfg.norm, p["ln"], x, ctx, name + "/ln")
        fn = mlstm_block_apply if kind == "mlstm" else slstm_block_apply
        y, new_state = fn(p["blk"], h, cfg.xlstm, cache, ctx, name + f"/{kind}")
        if active is not None and cache is not None:
            new_state = _row_select(_row_active(active), new_state, cache)
        x = x + y
        return x, new_state, x, _zero_aux()
    raise ValueError(kind)


# ==========================================================================
# Whole model
# ==========================================================================
def model_init(key: Array, cfg: ModelConfig) -> Params:
    keys = split_keys(key, cfg.n_layers + 4)
    p: Params = {}
    if cfg.input_kind in ("tokens", "mixed"):
        p["embed"] = embedding_init(keys[-1], cfg.padded_vocab, cfg.d_model,
                                    cfg.init_std, cfg.param_dtype)
    if cfg.input_kind in ("embeds", "mixed") and cfg.frontend_dim is not None:
        p["frontend_proj"] = linear_init(keys[-2], cfg.frontend_dim, cfg.d_model,
                                         dtype=cfg.param_dtype)
    if cfg.pos == "learned":
        p["pos_embed"] = positional_embedding_init(keys[-3], cfg.max_seq_len,
                                                   cfg.d_model, cfg.param_dtype)
    # layer groups
    glen = len(cfg.pattern)
    groups: List[Params] = []
    for g in range(cfg.n_groups):
        blocks = {}
        for i, kind in enumerate(cfg.pattern):
            blocks[f"b{i}"] = _block_init(keys[g * glen + i], cfg, kind)
        groups.append(blocks)
    if cfg.scan_layers and cfg.n_groups > 0:
        p["groups"] = tree_stack(groups)
    else:
        p["layers"] = groups
    # tail (non-divisible depths, e.g. recurrentgemma 38 = 12*3 + 2)
    tail = {}
    for i, kind in enumerate(cfg.tail_pattern):
        tail[f"t{i}"] = _block_init(keys[cfg.n_groups * glen + i], cfg, kind)
    if tail:
        p["tail"] = tail
    p["final_norm"] = norm_init(cfg.norm, cfg.d_model, cfg.param_dtype)
    if not cfg.tie_embeddings or cfg.input_kind == "embeds":
        p["lm_head"] = linear_init(keys[-4], cfg.d_model, cfg.padded_vocab,
                                   bias=False, std=cfg.init_std, dtype=cfg.param_dtype)
    return p


def _cache_entry(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                 dtype) -> Params:
    """Dense decode state of one block: KV tensors for attention blocks
    (ring buffer for local_attn), recurrent states otherwise."""
    hkv, dh = cfg.n_kv_heads, cfg.head_dim
    if kind in ("attn", "local_attn"):
        # local attention only ever needs `window` history (ring buffer)
        length = min(max_len, cfg.window) if (kind == "local_attn" and cfg.window) else max_len
        c = {
            "k": jnp.zeros((batch, length, hkv, dh), dtype),
            "v": jnp.zeros((batch, length, hkv, dh), dtype),
        }
        if kind == "local_attn" and cfg.window and length < cfg.max_seq_len:
            # per-row ring positions: slots decode at different offsets
            c["pos_ids"] = jnp.full((batch, length), -1, jnp.int32)
        return c
    if kind == "griffin":
        return griffin_init_state(batch, cfg.rglru, dtype)
    return xlstm_init_state(batch, kind, cfg.xlstm, dtype)


def _assemble_cache(cfg: ModelConfig, one) -> Params:
    """Mirror the param grouping (scan stacking + unrolled tail) so the layer
    scan can zip params with cache."""
    groups = [
        {f"b{i}": one(kind) for i, kind in enumerate(cfg.pattern)}
        for _ in range(cfg.n_groups)
    ]
    cache: Params = {}
    if cfg.scan_layers and cfg.n_groups > 0:
        cache["groups"] = tree_stack(groups)
    else:
        cache["layers"] = groups
    if cfg.tail_pattern:
        cache["tail"] = {f"t{i}": one(kind) for i, kind in enumerate(cfg.tail_pattern)}
    return cache


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=None) -> Params:
    """Dense per-layer decode state: every batch row reserves ``max_len`` KV
    positions up front. Simple and fully static, but pool memory scales with
    the worst-case length; ``init_paged_cache`` is the live-token-scaled
    alternative."""
    dtype = dtype or cfg.compute_dtype
    return _assemble_cache(cfg, partial(_cache_entry, cfg, batch=batch,
                                        max_len=max_len, dtype=dtype))


def init_paged_cache(cfg: ModelConfig, batch: int, max_len: int,
                     num_blocks: int, block_size: int = 16,
                     dtype=None, kv_int8: bool = False) -> Params:
    """Paged decode state (vLLM-style): each global-attention layer holds a
    shared head-major block pool ``k``/``v`` of shape
    (num_blocks, Hkv, block_size, Dh) — the Pallas kernel reads one
    (block_size, Dh) tile of it per grid step — plus a per-row
    ``block_table`` (batch, ceil(max_len / block_size)) of physical block
    ids (-1 = unallocated). Cache memory scales with *live tokens*
    (num_blocks * block_size across the whole batch) instead of
    batch * max_len, and ``max_len`` becomes a per-row logical cap only.

    Block tables are owned by the scheduler (host side): allocation and
    freeing happen outside jit, the tables are passed in as cache leaves, and
    the model only reads them — cache writes go through
    ``block_table[pos // block_size]`` indirection (see _attn_block_apply).
    Ring (local_attn) and recurrent states keep their dense per-row layout;
    they are already O(window) / O(1) per row.

    ``kv_int8=True`` stores the pools as int8 plus per-block scale rows
    ``k_scale``/``v_scale`` of shape (num_blocks, 1, block_size) — one f32
    scale per token slot, written by the same masked scatter that writes
    the pool (see quant.kv_cache). KV block memory drops ~3.5x for typical
    head shapes (``paged_kv_block_bytes``), so an equal-byte pool holds
    proportionally more blocks and admits proportionally more concurrent
    rows. Only the "attn" pools quantize; ring/recurrent state stays fp.
    """
    dtype = dtype or cfg.compute_dtype
    hkv, dh = cfg.n_kv_heads, cfg.head_dim
    if max_len % block_size:
        raise ValueError(
            f"max_len={max_len} must be a multiple of block_size="
            f"{block_size}: the block table covers exactly max_len "
            f"tokens")
    n_entries = max_len // block_size

    def one(kind: str):
        if kind == "attn":
            pool_dtype = jnp.int8 if kv_int8 else dtype
            c = {
                "k": jnp.zeros((num_blocks, hkv, block_size, dh), pool_dtype),
                "v": jnp.zeros((num_blocks, hkv, block_size, dh), pool_dtype),
                "block_table": jnp.full((batch, n_entries), -1, jnp.int32),
            }
            if kv_int8:
                c["k_scale"] = jnp.zeros((num_blocks, 1, block_size),
                                         jnp.float32)
                c["v_scale"] = jnp.zeros((num_blocks, 1, block_size),
                                         jnp.float32)
            return c
        return _cache_entry(cfg, kind, batch, max_len, dtype)

    return _assemble_cache(cfg, one)


def paged_kv_block_bytes(cfg: ModelConfig, block_size: int = 16,
                         kv_int8: bool = False, dtype=None) -> int:
    """Bytes ONE pool block costs per global-attention layer (k + v +, for
    int8, the two per-slot scale vectors). The capacity tests and the
    serving benchmark size fp and int8 pools to equal byte budgets with
    this, so 'admits Nx more rows at equal memory' is computed from the
    same accounting the pools actually allocate."""
    dtype = dtype or cfg.compute_dtype
    elems = block_size * cfg.n_kv_heads * cfg.head_dim
    if kv_int8:
        return 2 * elems * 1 + 2 * block_size * 4     # int8 kv + f32 scales
    return 2 * elems * jnp.dtype(dtype).itemsize


def copy_pool_blocks(cache: Params, src: Array, dst: Array) -> Params:
    """Copy physical pool blocks ``src[i] -> dst[i]`` in every paged pool
    of ``cache`` — K/V blocks and, for int8 KV, their per-slot scale
    vectors travel together (a block's scales are meaningless without it).

    This is the device half of the scheduler's copy-on-write: when a row
    must write into a block that other owners (the prefix trie, a
    sampling-group sibling) still reference, the host remaps the row's
    table entry to a fresh block and this helper materializes the content
    copy BEFORE the tick's forward lands any write. Each leaf is one
    fused gather-then-scatter (``leaf.at[dst].set(leaf[src])`` reads all
    sources from the pre-copy pool), so a pair whose source block was
    released and immediately re-allocated as another pair's destination
    still copies pre-copy content. Block tables and batch-led leaves
    (ring/recurrent state, dense KV) pass through untouched."""
    def copy_entry(entry):
        stacked = entry["block_table"].ndim == 3        # scanned: (G, B, W)
        out = dict(entry)
        for name in ("k", "v", "k_scale", "v_scale"):
            leaf = entry.get(name)
            if leaf is None:
                continue
            if stacked:
                out[name] = leaf.at[:, dst].set(leaf[:, src])
            else:
                out[name] = leaf.at[dst].set(leaf[src])
        return out

    def walk(node):
        if isinstance(node, dict):
            if "block_table" in node:
                return copy_entry(node)
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(walk(v) for v in node)
        return node

    return walk(cache)


def _embed_inputs(params: Params, cfg: ModelConfig, batch: Dict[str, Array],
                  pos, ctx: QuantContext) -> Array:
    scale = math.sqrt(cfg.d_model) if cfg.embed_scale else None
    parts = []
    if cfg.input_kind in ("embeds", "mixed") and "embeds" in batch:
        e = batch["embeds"].astype(cfg.compute_dtype)
        if "frontend_proj" in params:
            e = linear_apply(params["frontend_proj"], e, ctx, "frontend_proj")
        parts.append(e)
    if cfg.input_kind in ("tokens", "mixed") and "tokens" in batch:
        parts.append(
            embedding_apply(params["embed"], batch["tokens"], ctx, "embed", scale
                            ).astype(cfg.compute_dtype)
        )
    x = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    if cfg.pos == "learned":
        t = x.shape[1]
        positions = _positions(pos, t)        # (T,) or per-row (B, T)
        x = x + positional_embedding_apply(params["pos_embed"], positions).astype(x.dtype)
    return x


def model_apply(
    params: Params,
    cfg: ModelConfig,
    batch: Dict[str, Array],
    ctx: QuantContext = NO_QUANT,
    cache: Optional[Params] = None,
    pos: Any = 0,
    active: Optional[Array] = None,
    collect_acts: bool = False,
    paged_live_width: Optional[int] = None,
    paged_live_widths: Optional[Array] = None,
) -> Tuple[Array, Dict[str, Any]]:
    """Forward pass.

    batch: {"tokens": (B,T) int32} and/or {"embeds": (B,T,F)}.
    cache/pos: decode state; pass T=1 (or prefill chunk) with a cache.
    ``pos`` may be a shared scalar or a per-row (B,) vector (slot-pool
    decode); with a vector, cache writes scatter per row. ``active`` is an
    optional bool mask — per-row (B,) or per-token (B, T): masked entries
    still compute (their logits are garbage) but their cache/state writes
    are dropped — the masked-write contract the continuous batcher relies
    on. A per-token mask is what lets one fused step mix decode rows
    (1 live token) with prefill chunks (``counts[b]`` live tokens) of
    unequal lengths: row b's padding tail is simply inactive. Recurrent
    blocks (griffin/xlstm) reduce the mask per row (``any`` over tokens),
    so ragged rows are only supported for attention-family caches — the
    scheduler feeds recurrent models uniform-length steps.
    The cache may be dense (``init_cache``: per-row contiguous KV) or paged
    (``init_paged_cache``: global block pools + per-row block tables, writes
    routed through ``block_table[pos // block_size]``); the layout is
    detected per layer from the cache leaves, and both produce bitwise
    identical logits for the same tokens. ``paged_live_width`` (static int)
    optionally bounds the paged READ path to the first N block-table
    entries — allocation is prefix-dense, so the scheduler passes the
    bucketed max blocks-in-use per tick and the attention cost tracks live
    tokens instead of the table width (see ``paged_attention``).
    ``paged_live_widths`` ((B,) int32, optional) additionally masks each
    row's paged READ at its own block count rather than the tick max.
    Returns (logits (B,T,vocab) f32, aux) where aux may contain
    "attn_outputs" (stacked per-layer residual values) and "cache".
    """
    with jax.named_scope("embed"):
        x = _embed_inputs(params, cfg, batch, pos, ctx)
    b, t, _ = x.shape

    rope = None
    if cfg.pos == "rope":
        positions = _positions(pos, t)        # (T,) or per-row (B, T)
        rope = rope_angles(positions, cfg.head_dim, cfg.rope_theta)

    aux: Dict[str, Any] = {}
    acts: List[Array] = []

    def group_apply(x, gparams, gcache):
        new_gcache = {}
        gacts = []
        gaux = _zero_aux()
        for i, kind in enumerate(cfg.pattern):
            c = None if gcache is None else gcache[f"b{i}"]
            x, nc, a, ba = _block_apply(gparams[f"b{i}"], x, cfg, kind, rope, c, pos,
                                        ctx, f"layer_{kind}{i}", active=active,
                                        paged_live_width=paged_live_width,
                                        paged_live_widths=paged_live_widths)
            new_gcache[f"b{i}"] = nc
            gacts.append(a)
            gaux = {k: gaux[k] + ba[k] for k in gaux}
        return x, new_gcache, gacts, gaux

    new_cache: Optional[Params] = None
    if cfg.scan_layers and cfg.n_groups > 0:
        gfn = group_apply
        if cfg.remat and cache is None:
            policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                      if cfg.remat_policy == "dots"
                      else jax.checkpoint_policies.nothing_saveable)
            gfn = jax.checkpoint(group_apply, policy=policy)

        if cache is None:
            def scan_body_nc(x, gparams):
                x, _, gacts, gaux = gfn(x, gparams, None)
                return x, (jnp.stack([jnp.max(jnp.abs(a)) for a in gacts]), gaux)

            x, (act_stats, gauxs) = jax.lax.scan(scan_body_nc, x, params["groups"])
        else:
            def scan_body(x, inp):
                gparams, gcache = inp
                x, new_gcache, gacts, gaux = gfn(x, gparams, gcache)
                return x, (new_gcache,
                           jnp.stack([jnp.max(jnp.abs(a)) for a in gacts]), gaux)

            x, (new_caches, act_stats, gauxs) = jax.lax.scan(
                scan_body, x, (params["groups"], cache["groups"]))
            new_cache = {"groups": new_caches}
        aux["act_stats"] = act_stats
        aux["moe_aux"] = {k: jnp.sum(v) for k, v in gauxs.items()}
    else:
        new_cache = {"layers": []} if cache is not None else None
        moe_tot = _zero_aux()
        for g in range(cfg.n_groups):
            gparams = params["layers"][g] if "layers" in params else tree_slice(params["groups"], g)
            gcache = cache["layers"][g] if cache is not None else None
            x, ngc, gacts, gaux = group_apply(x, gparams, gcache)
            moe_tot = {k: moe_tot[k] + gaux[k] for k in moe_tot}
            if cache is not None:
                new_cache["layers"].append(ngc)
            acts.extend(gacts)
        aux["moe_aux"] = moe_tot

    # tail blocks (always unrolled)
    if cfg.tail_pattern:
        tcache_new = {}
        for i, kind in enumerate(cfg.tail_pattern):
            c = None if cache is None else cache["tail"][f"t{i}"]
            x, nc, a, ta = _block_apply(params["tail"][f"t{i}"], x, cfg, kind, rope, c,
                                        pos, ctx, f"tail_{kind}{i}", active=active,
                                        paged_live_width=paged_live_width,
                                        paged_live_widths=paged_live_widths)
            aux["moe_aux"] = {k: aux.get("moe_aux", _zero_aux())[k] + ta[k]
                              for k in ta}
            tcache_new[f"t{i}"] = nc
            acts.append(a)
        if cache is not None:
            new_cache["tail"] = tcache_new

    if acts and collect_acts:
        aux["attn_outputs"] = acts
    if cache is not None:
        aux["cache"] = new_cache

    with jax.named_scope("lm_head"):
        x = norm_apply(cfg.norm, params["final_norm"], x, ctx, "final_norm")
        if "lm_head" in params:
            logits = linear_apply(params["lm_head"], x, ctx, "lm_head").astype(jnp.float32)
        else:
            logits = embedding_attend(params["embed"], x, ctx, "lm_head")
        logits = softcap(logits, cfg.final_logit_softcap)
        if cfg.padded_vocab != cfg.vocab_size:
            pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
            logits = jnp.where(pad_mask, -1e30, logits)
    return logits, aux
