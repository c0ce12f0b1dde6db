"""Pallas paged-attention decode kernel vs the XLA gather oracle
(``paged_attention(..., backend="gather")``), interpret mode on CPU.

The sweep covers the full attention contract the gather path owns: GQA
ratios, causal + local-window masks over logical positions from ragged
per-row ``q_offset`` vectors, unallocated (-1) table entries, partially
filled tail blocks, logit soft-capping, vanilla vs clipped softmax
(gamma/zeta, including alpha-resolved gamma) vs gated attention, dtypes,
and the static ``live_width`` prefix slicing the scheduler uses. Pools are
head-major (num_blocks, Hkv, block_size, Dh); an alpha-resolved gamma uses
the case's logical length W*block_size as the model's fixed length.

Accumulation order differs (blockwise streaming vs materialized einsum),
so agreement is to f32 round-off (atol 2e-5; bf16 2e-2), not bitwise —
see kernels/paged_attention.py's module docstring.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.attention import AttentionConfig, paged_attention
from repro.core.softmax import ClippedSoftmaxConfig
from repro.kernels.paged_attention import (blocks_per_step, live_bound,
                                           live_steps, paged_mha)

KEY = jax.random.PRNGKey(0)


def _case(b=3, w=4, bs=8, hq=4, hkv=2, dh=16, tq=1, dtype=jnp.float32,
          seed=0, ragged=True):
    """Random pool + scrambled prefix-dense tables + ragged positions.

    Rows sit at unrelated positions; each owns exactly the blocks covering
    [0, pos + tq), so the last owned block is partially filled whenever
    pos + tq is not a block multiple."""
    nb = b * w + 2
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, tq, hq, dh), dtype)
    k_pool = jax.random.normal(ks[1], (nb, hkv, bs, dh), dtype)
    v_pool = jax.random.normal(ks[2], (nb, hkv, bs, dh), dtype)
    rng = np.random.default_rng(seed)
    max_pos = w * bs - tq
    pos = rng.integers(0, max_pos + 1, size=b) if ragged \
        else np.full(b, max_pos // 2)
    table = np.full((b, w), -1, np.int32)
    perm = rng.permutation(nb)
    nxt = 0
    for i in range(b):
        need = -(-(int(pos[i]) + tq) // bs)        # ceil: partial tail block
        table[i, :need] = perm[nxt:nxt + need]
        nxt += need
    gate = jax.nn.sigmoid(jax.random.normal(ks[3], (b, tq, hq))).astype(dtype)
    return (q, k_pool, v_pool, jnp.asarray(table),
            jnp.asarray(pos, jnp.int32), gate)


def _check(q, k_pool, v_pool, table, pos, cfg, gate=None, live_width=None,
           atol=2e-5):
    ref = paged_attention(q, k_pool, v_pool, table, cfg, q_offset=pos,
                          gate_pi=gate, backend="gather",
                          live_width=live_width)
    out = paged_attention(q, k_pool, v_pool, table, cfg, q_offset=pos,
                          gate_pi=gate, backend="kernel", interpret=True,
                          live_width=live_width)
    assert out.dtype == ref.dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


def _live_case(w, bs, hq, hkv, tq, int8=False, seed=0, b=4, dh=16):
    """Rows at ragged positions that own MORE blocks than their causal
    bound needs (up to two spare entries, as a speculative or
    just-grown row does) — so their ``live_widths`` lie past the causal
    bound — plus one row that holds nothing (live width 0)."""
    nb = b * w + 2
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    rng = np.random.default_rng(seed)
    q = jax.random.normal(ks[0], (b, tq, hq, dh), jnp.float32)
    if int8:
        k_pool = jax.random.randint(ks[1], (nb, hkv, bs, dh), -127, 128,
                                    jnp.int8)
        v_pool = jax.random.randint(ks[2], (nb, hkv, bs, dh), -127, 128,
                                    jnp.int8)
        scales = tuple(jax.random.uniform(k, (nb, 1, bs), jnp.float32,
                                          0.005, 0.05) for k in ks[3:5])
    else:
        k_pool = jax.random.normal(ks[1], (nb, hkv, bs, dh), jnp.float32)
        v_pool = jax.random.normal(ks[2], (nb, hkv, bs, dh), jnp.float32)
        scales = (None, None)
    pos = rng.integers(0, w * bs - tq + 1, size=b)
    pos[0] = 0
    table = np.full((b, w), -1, np.int32)
    live = np.zeros(b, np.int32)
    perm = rng.permutation(nb)
    nxt = 0
    for i in range(1, b):
        need = -(-(int(pos[i]) + tq) // bs)
        live[i] = min(w, need + int(rng.integers(0, 3)))
        table[i, :live[i]] = perm[nxt:nxt + live[i]]
        nxt += live[i]
    gate = jax.nn.sigmoid(jax.random.normal(ks[5], (b, tq, hq)))
    return (q, k_pool, v_pool, jnp.asarray(table), jnp.asarray(pos, jnp.int32),
            gate, live, scales)


SOFTMAXES = [
    ClippedSoftmaxConfig(),
    ClippedSoftmaxConfig(gamma=-0.03),
    ClippedSoftmaxConfig(gamma=-0.01, zeta=1.03),
    ClippedSoftmaxConfig(alpha=4.0),
]


class TestPagedKernelFast:
    """Small fixed cases per variant — fast tier (`-m "not slow"`)."""

    @pytest.mark.parametrize("sm", SOFTMAXES)
    def test_softmax_variants_ragged_positions(self, sm):
        q, kp, vp, tbl, pos, _ = _case()
        cfg = AttentionConfig(n_heads=4, n_kv_heads=2, d_head=16, softmax=sm,
                              seq_len=32)
        _check(q, kp, vp, tbl, pos, cfg)

    def test_gated_clipped(self):
        q, kp, vp, tbl, pos, gate = _case()
        cfg = AttentionConfig(n_heads=4, n_kv_heads=2, d_head=16,
                              softmax=ClippedSoftmaxConfig(gamma=-0.03))
        _check(q, kp, vp, tbl, pos, cfg, gate=gate)

    def test_local_window(self):
        q, kp, vp, tbl, pos, _ = _case(w=6)
        cfg = AttentionConfig(n_heads=4, n_kv_heads=2, d_head=16, window=11,
                              softmax=ClippedSoftmaxConfig(gamma=-0.02))
        _check(q, kp, vp, tbl, pos, cfg)

    def test_softcap(self):
        q, kp, vp, tbl, pos, _ = _case()
        cfg = AttentionConfig(n_heads=4, n_kv_heads=2, d_head=16,
                              logit_softcap=30.0, seq_len=32,
                              softmax=ClippedSoftmaxConfig(alpha=4.0))
        _check(q, kp, vp, tbl, pos, cfg)

    def test_live_width_slicing_exact(self):
        """Slicing the read to the allocated prefix must not change the
        result — including the alpha-resolved clip threshold, which comes
        from the config's fixed length, not the visited KV axis."""
        q, kp, vp, tbl, pos, _ = _case(w=8, seed=3)
        held = int(np.max(np.sum(np.asarray(tbl) >= 0, axis=1)))
        cfg = AttentionConfig(n_heads=4, n_kv_heads=2, d_head=16, seq_len=64,
                              softmax=ClippedSoftmaxConfig(alpha=4.0))
        full = paged_attention(q, kp, vp, tbl, cfg, q_offset=pos,
                               backend="gather")
        for backend in ("gather", "kernel"):
            sliced = paged_attention(q, kp, vp, tbl, cfg, q_offset=pos,
                                     backend=backend, interpret=True,
                                     live_width=held)
            np.testing.assert_allclose(np.asarray(sliced), np.asarray(full),
                                       atol=2e-5, err_msg=backend)

    def test_per_row_live_widths_exact(self):
        """Masking each row's gather read at its OWN block count (instead of
        the tick max) must be bitwise-neutral: allocation is prefix-dense,
        so the masked entries were -1 (already dead) — AND it must win when
        they are not: stale garbage ids beyond a row's count are hidden by
        the per-row mask where the bare -1 test would read them."""
        q, kp, vp, tbl, pos, _ = _case(w=8, seed=3)
        counts = np.sum(np.asarray(tbl) >= 0, axis=1)
        cfg = AttentionConfig(n_heads=4, n_kv_heads=2, d_head=16, seq_len=64,
                              softmax=ClippedSoftmaxConfig(alpha=4.0))
        lws = jnp.asarray(counts, jnp.int32)
        # at a FIXED table width the per-row mask is bitwise-neutral (the
        # masked entries contributed exact zeros already) — both without
        # and combined with the static live_width slice
        for lw in (None, int(counts.max())):
            full = paged_attention(q, kp, vp, tbl, cfg, q_offset=pos,
                                   backend="gather", live_width=lw)
            per_row = paged_attention(q, kp, vp, tbl, cfg, q_offset=pos,
                                      backend="gather", live_width=lw,
                                      live_widths=lws)
            np.testing.assert_array_equal(np.asarray(per_row),
                                          np.asarray(full), err_msg=str(lw))
        # stale ids beyond each row's count: the per-row mask must hide
        # them. Discriminating case needs causal=False — under a causal
        # mask those positions are unreachable anyway, which is exactly why
        # masking them is bitwise-free in the serving path.
        cfg_nc = AttentionConfig(n_heads=4, n_kv_heads=2, d_head=16,
                                 causal=False)
        full_nc = paged_attention(q, kp, vp, tbl, cfg_nc, q_offset=pos,
                                  backend="gather")
        stale = np.asarray(tbl).copy()
        for b in range(stale.shape[0]):
            stale[b, counts[b]:] = 0               # valid-looking garbage
        leaky = paged_attention(q, kp, vp, jnp.asarray(stale), cfg_nc,
                                q_offset=pos, backend="gather")
        assert not np.array_equal(np.asarray(leaky), np.asarray(full_nc))
        with_stale = paged_attention(q, kp, vp, jnp.asarray(stale), cfg_nc,
                                     q_offset=pos, backend="gather",
                                     live_widths=jnp.asarray(counts, jnp.int32))
        np.testing.assert_array_equal(np.asarray(with_stale),
                                      np.asarray(full_nc))

    def test_bf16(self):
        q, kp, vp, tbl, pos, gate = _case(dtype=jnp.bfloat16)
        cfg = AttentionConfig(n_heads=4, n_kv_heads=2, d_head=16,
                              softmax=ClippedSoftmaxConfig(gamma=-0.02))
        _check(q, kp, vp, tbl, pos, cfg, gate=gate, atol=2e-2)

    def test_unallocated_row_outputs_zero(self):
        """A row whose table is all -1 (never admitted) attends to nothing:
        both backends emit exact zeros for it."""
        q, kp, vp, tbl, pos, _ = _case()
        tbl = tbl.at[1].set(-1)
        cfg = AttentionConfig(n_heads=4, n_kv_heads=2, d_head=16,
                              softmax=ClippedSoftmaxConfig(gamma=-0.03))
        for backend in ("gather", "kernel"):
            out = paged_attention(q, kp, vp, tbl, cfg, q_offset=pos,
                                  backend=backend, interpret=True)
            assert not np.asarray(out[1]).any(), backend


# (table width W, block size, Hq, Hkv, Tq, int8 pools, softmax): P is the
# fewest entries filling 128 lanes, capped by W — 4 at block 32, 2 at 64 —
# so each row spans several grid steps, and W is no multiple of P
LIVE_CASES = {
    "ragged-G2": (10, 32, 4, 2, 1, False, ClippedSoftmaxConfig()),
    "ragged-G1-int8": (10, 32, 2, 2, 1, True, ClippedSoftmaxConfig()),
    "spec-k3-clipped": (9, 32, 4, 2, 4, False,
                        ClippedSoftmaxConfig(gamma=-0.03)),
    "spec-k3-clipped-int8": (9, 32, 2, 2, 4, True,
                             ClippedSoftmaxConfig(alpha=4.0)),
    "block64-G2-window": (7, 64, 4, 2, 2, False,
                          ClippedSoftmaxConfig(gamma=-0.02, zeta=1.03)),
}


class TestPagedKernelLiveBound:
    """The kernel's grid of P entries per step and its per-row live bound
    against the gather oracle given the same ``live_widths`` — fast tier."""

    @pytest.mark.parametrize("name", sorted(LIVE_CASES))
    def test_ragged_live_widths(self, name):
        w, bs, hq, hkv, tq, int8, sm = LIVE_CASES[name]
        q, kp, vp, tbl, pos, gate, live, (ks, vs) = _live_case(
            w, bs, hq, hkv, tq, int8=int8, seed=len(name))
        p = blocks_per_step(bs, w)
        bound = live_bound(np.asarray(pos), tq, live, block_size=bs, width=w)
        assert w % p and 1 < p < w
        assert live[0] == 0 and (bound % p).any()     # empty row; mid-step end
        assert (bound < live).any()                   # causal bound binds
        cfg = AttentionConfig(n_heads=hq, n_kv_heads=hkv, d_head=16,
                              softmax=sm, seq_len=w * bs,
                              window=20 if "window" in name else None)
        kw = dict(q_offset=pos, gate_pi=gate, k_scale=ks, v_scale=vs,
                  live_widths=jnp.asarray(live))
        ref = paged_attention(q, kp, vp, tbl, cfg, backend="gather", **kw)
        out = paged_attention(q, kp, vp, tbl, cfg, backend="kernel",
                              interpret=True, **kw)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5 if not int8 else 2e-4)
        assert not np.asarray(out[0]).any()           # nothing live: zeros

    def test_step_counts(self):
        """``live_steps``: min(live width, causal bound, W) entries, P per
        step; numpy in, numpy out."""
        pos = np.array([0, 0, 37, 100, 5])
        live = np.array([0, 3, 9, 10, 10])
        assert blocks_per_step(32, 10) == 4 and blocks_per_step(8, 4) == 4
        bound = live_bound(pos, 4, live, block_size=32, width=10)
        np.testing.assert_array_equal(bound, [0, 1, 2, 4, 1])
        steps = live_steps(pos, 4, live, block_size=32, width=10)
        assert isinstance(steps, np.ndarray)
        np.testing.assert_array_equal(steps, [0, 1, 1, 1, 1])
        np.testing.assert_array_equal(
            live_steps(pos, 4, None, block_size=32, width=10, causal=False),
            [3] * 5)
        np.testing.assert_array_equal(
            live_steps(pos + 300, 4, live, block_size=32, width=10),
            [0, 1, 3, 3, 3])


class TestPagedKernelSweep:
    """Wider parametrized sweep — slow tier."""

    pytestmark = pytest.mark.slow

    @pytest.mark.parametrize("group", [1, 2, 4])
    @pytest.mark.parametrize("sm", SOFTMAXES)
    @pytest.mark.parametrize("window", [None, 13])
    def test_gqa_window_softmax(self, group, sm, window):
        hkv = 2
        q, kp, vp, tbl, pos, gate = _case(hq=group * hkv, hkv=hkv, w=5,
                                          seed=group)
        cfg = AttentionConfig(n_heads=group * hkv, n_kv_heads=hkv, d_head=16,
                              window=window, softmax=sm, seq_len=40)
        _check(q, kp, vp, tbl, pos, cfg, gate=gate)

    @pytest.mark.parametrize("tq", [2, 5])
    def test_multi_token_query_block(self, tq):
        """Tq > 1 (speculative / chunked-prefill shapes): causal masking
        inside the query block over logical positions."""
        q, kp, vp, tbl, pos, gate = _case(tq=tq, w=5, seed=tq)
        cfg = AttentionConfig(n_heads=4, n_kv_heads=2, d_head=16, seq_len=40,
                              softmax=ClippedSoftmaxConfig(alpha=4.0))
        _check(q, kp, vp, tbl, pos, cfg, gate=gate)

    @pytest.mark.parametrize("seed", range(4))
    def test_random_tables(self, seed):
        q, kp, vp, tbl, pos, _ = _case(b=4, w=7, bs=4, seed=10 + seed)
        cfg = AttentionConfig(n_heads=4, n_kv_heads=2, d_head=16,
                              softmax=ClippedSoftmaxConfig(gamma=-0.05))
        _check(q, kp, vp, tbl, pos, cfg)

    def test_scalar_offset(self):
        q, kp, vp, tbl, pos, _ = _case(b=2, ragged=False)
        cfg = AttentionConfig(n_heads=4, n_kv_heads=2, d_head=16)
        _check(q, kp, vp, tbl, int(pos[0]), cfg)


class TestKernelEndToEnd:
    @pytest.mark.slow
    def test_batcher_tokens_identical_with_kernel_backend(self):
        """The whole serving stack over the Pallas read path (interpret
        mode) emits the same greedy tokens as the gather path / sequential
        generate — the kernel drops into the fused tick unchanged."""
        from repro.models import model_init
        from repro.models.transformer import ModelConfig
        from repro.serving import ContinuousBatcher, GenerateConfig, Request, generate

        base = ModelConfig(name="tiny", n_layers=2, d_model=32, n_heads=4,
                           n_kv_heads=2, d_ff=64, vocab_size=64, pos="rope",
                           max_seq_len=1024, train_seq_len=32,
                           scan_layers=False, remat=False,
                           mlp_kind="swiglu", norm="rmsnorm",
                           softmax_cfg=ClippedSoftmaxConfig(alpha=4.0))
        params = model_init(KEY, base)
        rng = np.random.default_rng(5)
        prompts = [rng.integers(4, 60, size=n).astype(np.int32)
                   for n in (6, 4)]
        refs = [np.asarray(generate(params, base, jnp.asarray(p)[None, :],
                                    GenerateConfig(max_new_tokens=5))[0, len(p):])
                for p in prompts]
        cfg = dataclasses.replace(base, paged_backend="kernel")
        b = ContinuousBatcher(params, cfg, batch_size=2, max_len=32,
                              paged=True, block_size=8)
        for u, p in enumerate(prompts):
            b.submit(Request(uid=u, prompt=p, max_new_tokens=5))
        out = {r.uid: r.output for r in b.run()}
        for u, ref in enumerate(refs):
            np.testing.assert_array_equal(out[u], ref, err_msg=f"uid={u}")
