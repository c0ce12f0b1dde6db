"""Fused serving decode: prefill + a fully-jitted token-generation loop.

``generate`` runs the whole decode as ONE compiled program — a
``lax.while_loop`` that samples (greedy / temperature / top-k), honors
``eos_id`` with a per-row finished mask (later positions are padded with
``pad_id``), and early-exits once every row is finished. There is no
per-token python dispatch; (prefill, decode) are the same functions the
decode_* dry-run cells lower, and ``decode_one`` accepts per-row positions
plus an active mask so the continuous batcher shares the exact same step.

The two per-row contracts the batcher builds on (both live in
``model_apply`` / ``core.attention``, documented here because this module is
their serving entry point):

  * ``pos`` / ``q_offset`` vectors — every position argument may be a shared
    scalar OR a per-row (B,) int32 vector. With a vector, row b's query
    block sits at absolute position ``pos[b]`` (RoPE angles, learned
    positional embeds and attention masks all index per row), which is what
    lets one fused step decode a batch whose rows are at unrelated
    positions.
  * masked scatter cache writes — with vector ``pos``, KV-cache updates are
    per-row scatters at ``pos[b]``; rows with ``active[b] == False`` have
    their write index redirected out of bounds and dropped (jax scatter
    ``mode="drop"``), so a dead or stalled row's cache is left bit-exact
    untouched without any save/restore double buffering.

``generate`` itself always uses the dense contiguous cache (a standalone
batch has no reuse to exploit); the paged block-pool cache is a scheduler
concern — see ``repro.serving.scheduler`` and ``docs/serving.md``.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.transformer import ModelConfig, init_cache, model_apply
from repro.quant.qconfig import NO_QUANT, QuantContext

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class GenerateConfig:
    max_new_tokens: int = 32
    temperature: float = 0.0       # 0 => greedy
    top_k: Optional[int] = None    # sample only among the k best logits
    eos_id: Optional[int] = None   # a row stops after emitting this token
    pad_id: int = 0                # fills positions after EOS


def sample_logits(logits: Array, gen: GenerateConfig,
                  key: Optional[Array] = None) -> Array:
    """(B, vocab) logits -> (B,) int32 token ids."""
    if gen.temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if key is None:
        raise ValueError("sample_logits needs a PRNG key when temperature > 0")
    if gen.top_k is not None and 0 < gen.top_k < logits.shape[-1]:
        kth = jax.lax.top_k(logits, gen.top_k)[0][:, -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    return jax.random.categorical(key, logits / gen.temperature).astype(jnp.int32)


def sample_token_at(logits: Array, gen: GenerateConfig, key: Array,
                    target_pos) -> Array:
    """(vocab,) logits -> () int32 token id for ONE row, keyed by the
    token's absolute position.

    The continuous batcher's sampling rule: the token that will sit at
    logical position p is drawn with ``fold_in(request_key, p)``. Keying by
    *position* rather than by draw order makes sampling a pure function of
    (request seed, position), so a preempted request recomputed from its
    prompt + generated-so-far resamples the identical continuation — the
    sampling analogue of the greedy recompute-resume guarantee."""
    if gen.temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    k = jax.random.fold_in(key, jnp.asarray(target_pos, jnp.int32))
    return sample_logits(logits[None], gen, k)[0]


def sample_rows(logits: Array, gen: GenerateConfig, keys: Array,
                target_pos: Array) -> Array:
    """Per-row batched ``sample_token_at``: (B, vocab) logits, (B, 2)
    uint32 per-request keys, (B,) target positions -> (B,) int32 tokens.
    The fused-tick sampler of ``ContinuousBatcher``."""
    if gen.temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.vmap(lambda l, k, p: sample_token_at(l, gen, k, p))(
        logits, keys, target_pos)


def sample_rows_all(logits: Array, gen: GenerateConfig, keys: Array,
                    pos: Array) -> Array:
    """Every-position sampler for the speculative tick: (B, T, vocab)
    logits, (B, 2) uint32 keys, (B,) row start positions -> (B, T) int32.

    Entry ``[b, j]`` is the token plain decoding would place at absolute
    position ``pos[b] + j + 1``, sampled from ``logits[b, j]`` under the
    position-keyed rule (``fold_in(key_b, pos_b + j + 1)``; greedy is
    argmax). The verifier's accept test compares draft tokens against
    these entries, so speculation inherits bitwise equality with plain
    decoding from the same invariance that makes chunked prefill and
    recompute-resume exact. Padding positions (j >= the row's real token
    count) produce garbage entries the host never reads."""
    if gen.temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    t = logits.shape[1]
    tpos = pos[:, None] + 1 + jnp.arange(t, dtype=jnp.int32)[None, :]
    per_row = jax.vmap(lambda l, k, p: sample_token_at(l, gen, k, p),
                       in_axes=(0, None, 0))
    return jax.vmap(per_row)(logits, keys, tpos)


def prefill(params, cfg: ModelConfig, tokens: Array, max_len: int):
    """Run the prompt through the model, building the KV cache.

    Returns (last_logits (B, vocab), cache, prompt_len)."""
    b, t = tokens.shape
    cache = init_cache(cfg, b, max_len)
    logits, aux = model_apply(params, cfg, {"tokens": tokens},
                              cache=cache, pos=0)
    return logits[:, -1, :], aux["cache"], t


def _ring_chunk_cap(cfg: ModelConfig, max_len: int) -> Optional[int]:
    """Largest prefill chunk a ``local_attn`` ring admits (the batcher's
    ``ring_cap``): a chunk must fit the ring and its own writes must not
    collide inside it. None when no layer uses a ring cache."""
    kinds = tuple(cfg.pattern) + tuple(cfg.tail_pattern)
    if any(k == "local_attn" for k in kinds) and cfg.window:
        return min(max_len, cfg.window)
    return None


def chunked_prefill(params, cfg: ModelConfig, tokens: Array, max_len: int,
                    chunk: Optional[int] = None):
    """Stream the prompt through ``step_rows`` in uniform chunks — the
    batcher's chunked-prefill contract (per-row pos vectors + per-token
    active masks), usable standalone. Unlike one-shot ``prefill`` this
    works for ``local_attn`` prompts longer than the window: each chunk is
    capped at the ring so the pre-write ring read path sees a consistent
    window (see ``model_apply``).

    Returns (last_logits (B, vocab), cache, prompt_len)."""
    b, t = tokens.shape
    cap = _ring_chunk_cap(cfg, max_len)
    step = min(x for x in (chunk, cap, t) if x is not None and x > 0)
    cache = init_cache(cfg, b, max_len)
    last = None
    for off in range(0, t, step):
        c = min(step, t - off)
        pos = jnp.full((b,), off, jnp.int32)
        counts = jnp.full((b,), c, jnp.int32)
        last, cache = step_rows(params, cfg, cache,
                                tokens[:, off:off + c], pos, counts)
    return last, cache, t


def decode_one(params, cfg: ModelConfig, cache, tokens: Array, pos,
               active: Optional[Array] = None):
    """One decode step. ``pos`` is a shared scalar or per-row (B,) vector;
    ``active`` masks cache writes of dead rows (see model_apply)."""
    logits, aux = model_apply(params, cfg, {"tokens": tokens},
                              cache=cache, pos=pos, active=active)
    return logits[:, -1, :], aux["cache"]


def step_rows(params, cfg: ModelConfig, cache, tokens: Array, pos: Array,
              counts: Array, paged_live_width: Optional[int] = None,
              paged_live_widths: Optional[Array] = None,
              ctx: QuantContext = NO_QUANT):
    """Variable-Tq fused step: the token-budget scheduler's mixed
    prefill/decode forward.

    ``tokens`` (B, T) carries every row's contribution for this tick,
    left-aligned: a decode row holds 1 token, a prefill row holds a chunk
    of its prompt, an idle row holds padding. ``pos`` (B,) is each row's
    absolute start position and ``counts`` (B,) its number of REAL tokens
    (0 = idle); the derived per-token active mask drops every padding
    token's cache write (see ``model_apply``). Returns
    (last_logits (B, vocab), cache) where ``last_logits[b]`` is the logits
    at row b's LAST real token — the only position whose prediction the
    scheduler may consume (chunk-aware sampling: a non-final prefill chunk
    discards it, the final chunk samples the request's first token from
    it, a decode row samples its next token).

    ``ctx``: optional QuantContext in 'int8' mode — the W8A8 serving path.
    Its calibrated ranges are python-float closure constants, so the tick
    stays jit-safe; the context is captured, not traced."""
    logits, cache = step_rows_full(
        params, cfg, cache, tokens, pos, counts,
        paged_live_width=paged_live_width,
        paged_live_widths=paged_live_widths, ctx=ctx)
    counts = jnp.asarray(counts, jnp.int32)
    last = jnp.take_along_axis(
        logits, jnp.maximum(counts - 1, 0)[:, None, None], axis=1)[:, 0, :]
    return last, cache


def step_rows_full(params, cfg: ModelConfig, cache, tokens: Array,
                   pos: Array, counts: Array,
                   paged_live_width: Optional[int] = None,
                   paged_live_widths: Optional[Array] = None,
                   ctx: QuantContext = NO_QUANT):
    """``step_rows`` returning ALL positions' logits (B, T, vocab) — the
    speculative tick's forward, where EVERY fed position's prediction is
    consumed (position j's logits decide the fate of draft token j+1).
    Same masked-scatter write contract: padding tokens (j >= counts[b])
    write nothing; *rejected draft* tokens DO write, which is sound
    because every read path masks by logical position — see
    ``make_spec_step``."""
    b, t = tokens.shape
    counts = jnp.asarray(counts, jnp.int32)
    active = jnp.arange(t, dtype=jnp.int32)[None, :] < counts[:, None]
    logits, aux = model_apply(params, cfg, {"tokens": tokens}, ctx=ctx,
                              cache=cache, pos=pos, active=active,
                              paged_live_width=paged_live_width,
                              paged_live_widths=paged_live_widths)
    return logits, aux["cache"]


def mixed_tick(cfg: ModelConfig, gen: GenerateConfig,
               ctx: QuantContext = NO_QUANT):
    """The fused engine tick as a plain (unjitted) function: one
    ``step_rows`` forward advancing every runnable row at its own position
    — decode rows by 1 token, prefill rows by a chunk; padding tokens'
    writes are dropped inside model_apply (masked per-token scatter) —
    followed by position-keyed sampling. Returns ``(next tokens, cache,
    last-position logits)``; ``make_mixed_step`` jits it without the
    logits. ``live_width`` (static) bounds the paged attention read to the
    allocated block-table prefix and ``live_widths`` masks each row's read
    at its own block count; ``keys`` are per-request PRNG keys — the
    sampled token at position p is ``fold_in(key, p)``, so recompute-resume
    (and swap resume) replay identical samples. ``ctx`` carries calibrated
    int8 ranges as jit closure constants (the W8A8 tick)."""

    def tick(params, cache, tokens, pos, counts, keys, live_width,
             live_widths):
        last, new_cache = step_rows(
            params, cfg, cache, tokens, pos, counts,
            paged_live_width=live_width, paged_live_widths=live_widths,
            ctx=ctx)
        with jax.named_scope("sample"):
            nxt = sample_rows(last, gen, keys, pos + counts)
        return nxt, new_cache, last

    return tick


def make_mixed_step(cfg: ModelConfig, gen: GenerateConfig,
                    ctx: QuantContext = NO_QUANT):
    """Build the jitted fused engine tick ``ContinuousBatcher`` runs every
    step (``mixed_tick``), returning ``(next tokens, cache)``."""
    tick = mixed_tick(cfg, gen, ctx)

    def _mixed_step(*args):
        nxt, new_cache, _ = tick(*args)
        return nxt, new_cache

    return jax.jit(_mixed_step, static_argnums=(6,))


def make_spec_step(cfg: ModelConfig, gen: GenerateConfig,
                   ctx: QuantContext = NO_QUANT):
    """Build the jitted SPECULATIVE engine tick: one ``step_rows_full``
    forward verifying up to k draft tokens per decode row in a single
    variable-Tq read, returning the full (B, T) target-token matrix
    instead of one token per row.

    A decode row feeds ``[last_token, d_1 .. d_k]`` at its position; the
    returned ``tgt[b, j]`` is what plain decoding would emit at position
    ``pos[b] + j + 1``, so the host accepts the longest prefix of drafts
    with ``d_j == tgt[b, j-1]`` and always banks the bonus token
    ``tgt[b, n_acc]`` — 1..k+1 tokens per tick, bitwise identical to the
    non-speculative stream (see ``sample_rows_all``). Prefill rows ride
    the same forward unchanged: a final chunk's first token is
    ``tgt[b, c-1]``, exactly what ``make_mixed_step`` would have sampled,
    so one program serves the whole mixed tick.

    Rejected drafts HAVE already scattered their K/V into the cache when
    verification happens (write and read are one fused program). That is
    sound for global-attn caches, dense or paged, fp or int8: (a) every
    read path masks keys by logical position (causal mask / live-width
    mask over positions <= q), so entries past a row's accepted position
    are causally invisible; (b) the row's next writes land at those same
    positions and overwrite the stale entries before its position
    advances past them; (c) KV bits (incl. int8 quantize-at-write) are
    pure functions of (token, position), so the overwrite equals what a
    non-speculative tick would have written. It is NOT sound for ring
    (``local_attn``) or recurrent state — a ring write at pos % window
    clobbers live in-window history and a recurrence has no position to
    mask — which is why the scheduler refuses ``spec=`` for those
    configs. ``live_width`` stays the static pow-2-bucketed argument and
    T is bucketed by the scheduler, so the speculative tick adds at most
    log2(k+1) extra specializations."""

    def _spec_step(params, cache, tokens, pos, counts, keys,
                   live_width, live_widths):
        logits, new_cache = step_rows_full(
            params, cfg, cache, tokens, pos, counts,
            paged_live_width=live_width, paged_live_widths=live_widths,
            ctx=ctx)
        tgt = sample_rows_all(logits, gen, keys, pos)
        return tgt, new_cache

    return jax.jit(_spec_step, static_argnums=(6,))


@partial(jax.jit, static_argnums=(1, 4))
def _decode_loop(params, cfg: ModelConfig, cache, last_logits,
                 gen: GenerateConfig, pos, key):
    """Jitted whole-loop decode: returns ((B, max_new_tokens) tokens, cache).

    Token 0 comes from the prefill logits; each loop iteration decodes then
    samples, so no forward pass is wasted on the final token. The finished
    mask makes rows emit ``pad_id`` after EOS and the loop exits early once
    every row is done (EOS/length masking)."""
    b = last_logits.shape[0]
    n = gen.max_new_tokens
    if n == 0:
        return jnp.zeros((b, 0), jnp.int32), cache
    key, sub = jax.random.split(key)
    tok = sample_logits(last_logits, gen, sub)
    finished = tok == gen.eos_id if gen.eos_id is not None \
        else jnp.zeros((b,), jnp.bool_)
    buf = jnp.full((b, n), gen.pad_id, jnp.int32).at[:, 0].set(tok)
    pos = jnp.asarray(pos, jnp.int32)

    def cond(state):
        i, _, _, finished, _, _ = state
        return (i < n) & ~jnp.all(finished)

    def body(state):
        i, key, tok, finished, cache, buf = state
        logits, cache = decode_one(params, cfg, cache, tok[:, None],
                                   pos + i - 1)
        key, sub = jax.random.split(key)
        nxt = sample_logits(logits, gen, sub)
        if gen.eos_id is not None:
            nxt = jnp.where(finished, gen.pad_id, nxt)
            finished = finished | (nxt == gen.eos_id)
        buf = buf.at[:, i].set(nxt)
        return (i + 1, key, nxt, finished, cache, buf)

    state = (jnp.asarray(1, jnp.int32), key, tok, finished, cache, buf)
    _, _, _, _, cache, buf = jax.lax.while_loop(cond, body, state)
    return buf, cache


def generate(params, cfg: ModelConfig, prompt: Array, gen: GenerateConfig,
             key: Optional[Array] = None,
             prefill_chunk: Optional[int] = None) -> Array:
    """Greedy/temperature/top-k sampling. prompt: (B, T) int32. Returns
    (B, T + max_new_tokens); rows that emit ``gen.eos_id`` keep it and are
    padded with ``gen.pad_id`` afterwards.

    Prompts that overflow a ``local_attn`` ring (T > window) are prefilled
    through the batcher's chunked path automatically; ``prefill_chunk``
    forces chunked prefill with the given chunk size (it is still capped
    at the ring)."""
    t = prompt.shape[1]
    max_len = t + gen.max_new_tokens
    cap = _ring_chunk_cap(cfg, max_len)
    if prefill_chunk is None and (cap is None or t <= cap):
        last_logits, cache, pos = prefill(params, cfg, prompt, max_len)
    else:
        last_logits, cache, pos = chunked_prefill(
            params, cfg, prompt, max_len, chunk=prefill_chunk)
    key = key if key is not None else jax.random.PRNGKey(0)
    new_tokens, _ = _decode_loop(params, cfg, cache, last_logits, gen, pos, key)
    return jnp.concatenate([prompt, new_tokens], axis=1)
