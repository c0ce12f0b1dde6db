"""Paged KV cache: paged-vs-dense decode equivalence (bitwise), block
free-list reclamation, admission beyond the dense per-slot budget, the >=2x
short-request capacity win at equal pool memory, and recompute preemption
when the pool over-commits."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_models import opt_tiny
from repro.models import model_init
from repro.models.transformer import (
    ModelConfig,
    init_cache,
    init_paged_cache,
    model_apply,
)
from repro.serving import (
    BlockAllocator,
    ContinuousBatcher,
    GenerateConfig,
    Request,
    generate,
)

KEY = jax.random.PRNGKey(0)


def _setup(vocab=64):
    cfg = dataclasses.replace(opt_tiny(vocab=vocab, seq_len=32), max_seq_len=64)
    return cfg, model_init(KEY, cfg)


def _tiny(**kw):
    """Smallest config that still exercises attention + mlp, for tests whose
    cost is dominated by the number of prefills rather than realism."""
    base = dict(name="tiny", n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
                d_ff=64, vocab_size=64, pos="rope", max_seq_len=1024,
                scan_layers=False, remat=False, mlp_kind="swiglu",
                norm="rmsnorm")
    base.update(kw)
    return ModelConfig(**base)


def _refs(params, cfg, prompts, max_new):
    return [np.asarray(generate(params, cfg, jnp.asarray(p)[None, :],
                                GenerateConfig(max_new_tokens=m))[0, len(p):])
            for p, m in zip(prompts, max_new)]


def _ref_free(params, cfg, prompt, max_new):
    """Cache-free greedy oracle: grow the sequence one token at a time with
    full forward passes. Ground truth even where ``generate`` cannot go
    (a local_attn prompt longer than the window wraps its one-shot ring
    prefill)."""
    seq = list(map(int, prompt))
    out = []
    for _ in range(max_new):
        logits, _ = model_apply(params, cfg,
                                {"tokens": jnp.asarray([seq], jnp.int32)})
        tok = int(jnp.argmax(logits[0, -1]))
        out.append(tok)
        seq.append(tok)
    return np.asarray(out, np.int32)


def _run_batcher(params, cfg, prompts, max_new, **kw):
    b = ContinuousBatcher(params, cfg, **kw)
    for u, (p, m) in enumerate(zip(prompts, max_new)):
        b.submit(Request(uid=u, prompt=p, max_new_tokens=m))
    out = {r.uid: r.output for r in b.run()}
    return b, out


class TestPagedModelApply:
    def test_prefill_and_decode_bitwise_match_dense(self):
        """Same tokens through a scrambled-block-table paged cache and a
        dense cache produce bitwise identical logits (prefill + one fused
        per-row decode step with an active mask)."""
        cfg, params = _setup()
        prompt = jnp.arange(4, 12, dtype=jnp.int32)[None, :]
        dl, daux = model_apply(params, cfg, {"tokens": prompt},
                               cache=init_cache(cfg, 1, 32), pos=0)
        pcache = init_paged_cache(cfg, 1, 32, num_blocks=6, block_size=8)
        table = jnp.asarray([[2, 0, 3, -1]], jnp.int32)   # scrambled physical

        def set_table(path, leaf):
            if path and path[-1] == jax.tree_util.DictKey("block_table"):
                return jnp.broadcast_to(table, leaf.shape[:-2] + table.shape)
            return leaf

        pcache = jax.tree_util.tree_map_with_path(set_table, pcache)
        pl, paux = model_apply(params, cfg, {"tokens": prompt},
                               cache=pcache, pos=0)
        np.testing.assert_array_equal(np.asarray(dl), np.asarray(pl))

        tok = jnp.argmax(dl[:, -1:], -1).astype(jnp.int32)
        posv, act = jnp.asarray([8], jnp.int32), jnp.asarray([True])
        dl2, _ = model_apply(params, cfg, {"tokens": tok},
                             cache=daux["cache"], pos=posv, active=act)
        pl2, _ = model_apply(params, cfg, {"tokens": tok},
                             cache=paux["cache"], pos=posv, active=act)
        np.testing.assert_array_equal(np.asarray(dl2), np.asarray(pl2))

    def test_inactive_rows_do_not_write_pool(self):
        """active=False rows must not touch the shared pool — the paged form
        of the masked-scatter contract (a clobbered pool block would corrupt
        ANOTHER request, not just the dead row)."""
        cfg, params = _setup()
        cache = init_paged_cache(cfg, 2, 32, num_blocks=8, block_size=8)
        table = jnp.asarray([[0, 1, -1, -1], [2, 3, -1, -1]], jnp.int32)

        def set_table(path, leaf):
            if path and path[-1] == jax.tree_util.DictKey("block_table"):
                return table
            return leaf

        cache = jax.tree_util.tree_map_with_path(set_table, cache)
        toks = jnp.asarray([[5], [9]], jnp.int32)
        _, aux = model_apply(params, cfg, {"tokens": toks}, cache=cache,
                             pos=jnp.asarray([3, 7], jnp.int32),
                             active=jnp.asarray([True, False]))
        for g, gn in zip(init_paged_cache(cfg, 2, 32, 8, 8)["layers"],
                         aux["cache"]["layers"]):
            for name in g:
                for kv in ("k", "v"):
                    new = np.asarray(gn[name][kv])
                    # row 1 owns blocks 2/3; its write (pos 7 -> block 0 of
                    # its table = pool block 2) must have been dropped
                    assert not new[2:4].any()
                    # row 0 wrote pos 3 -> its block 0 = pool block 0
                    assert new[0].any()


class TestPagedVsDenseBatcher:
    @pytest.mark.slow
    def test_same_tokens_for_same_prompts(self):
        """Dense and paged batchers emit identical greedy tokens, both equal
        to a dedicated sequential generate per request (exact match)."""
        cfg, params = _setup()
        rng = np.random.default_rng(3)
        prompts = [rng.integers(4, 60, size=n).astype(np.int32)
                   for n in (5, 3, 8, 4, 6)]
        max_new = [6, 8, 5, 7, 6]
        refs = _refs(params, cfg, prompts, max_new)
        _, dense = _run_batcher(params, cfg, prompts, max_new,
                                batch_size=2, max_len=32)
        _, paged = _run_batcher(params, cfg, prompts, max_new,
                                batch_size=2, max_len=32,
                                paged=True, block_size=8)
        for u, ref in enumerate(refs):
            np.testing.assert_array_equal(dense[u], ref, err_msg=f"uid={u}")
            np.testing.assert_array_equal(paged[u], ref, err_msg=f"uid={u}")

    @pytest.mark.slow
    def test_clipped_softmax_paged_matches_dense(self):
        """gamma = -alpha/T resolves from the config's training length on
        both backends, so paged and dense batchers stay exactly equal under
        the paper's clipped softmax, not just vanilla."""
        from repro.configs import apply_method
        cfg, _ = _setup()
        cfg = apply_method(cfg, "clipped_softmax", alpha=4.0)
        params = model_init(KEY, cfg)
        rng = np.random.default_rng(13)
        prompts = [rng.integers(4, 60, size=n).astype(np.int32)
                   for n in (5, 7, 4)]
        max_new = [6, 5, 7]
        _, dense = _run_batcher(params, cfg, prompts, max_new,
                                batch_size=2, max_len=32)
        _, paged = _run_batcher(params, cfg, prompts, max_new,
                                batch_size=2, max_len=32,
                                paged=True, block_size=8)
        for u in range(len(prompts)):
            np.testing.assert_array_equal(paged[u], dense[u], err_msg=f"uid={u}")

    def test_block_size_must_divide_max_len(self):
        cfg = _tiny()
        with pytest.raises(ValueError, match="multiple of block_size"):
            init_paged_cache(cfg, 1, 20, num_blocks=4, block_size=8)

    def test_mixed_pattern_ring_plus_paged(self):
        """Patterns mixing global attn (paged pool) with local_attn (dense
        ring) must admit and decode correctly: admission prefills against a
        batch-1 view (fresh ring row + live pools), not the batch-B cache.
        Two sequential occupants of the same slot also guard against stale
        ring pos_ids leaking into the second request's prefill."""
        cfg = _tiny(pattern=("attn", "local_attn"), window=16, max_seq_len=64)
        params = model_init(KEY, cfg)
        rng = np.random.default_rng(5)
        prompts = [rng.integers(4, 60, size=n).astype(np.int32)
                   for n in (6, 4, 8)]
        max_new = [5, 6, 4]
        refs = _refs(params, cfg, prompts, max_new)
        _, out = _run_batcher(params, cfg, prompts, max_new,
                              batch_size=1, max_len=32,
                              paged=True, block_size=8)
        for u, ref in enumerate(refs):
            np.testing.assert_array_equal(out[u], ref, err_msg=f"uid={u}")

    @pytest.mark.slow
    def test_scanned_layers_paged(self):
        """Scanned caches stack the pools (G, num_blocks, H, bs, D) and the
        tables (G, B, W); the batcher must thread both through lax.scan."""
        cfg = _tiny(scan_layers=True, max_seq_len=64)
        params = model_init(KEY, cfg)
        p = np.arange(4, 9, dtype=np.int32)
        ref = _refs(params, cfg, [p], [4])[0]
        _, out = _run_batcher(params, cfg, [p], [4], batch_size=2, max_len=32,
                              paged=True, block_size=8)
        np.testing.assert_array_equal(out[0], ref)


class TestBlockAccounting:
    def test_free_list_reclaimed_after_run(self):
        """Every block returns to the free list after retirement — no leak
        across repeated run() generations on the same batcher."""
        cfg = _tiny(max_seq_len=64)
        params = model_init(KEY, cfg)
        rng = np.random.default_rng(0)
        b = ContinuousBatcher(params, cfg, batch_size=2, max_len=32,
                              paged=True, block_size=8, num_blocks=8)
        for generation in range(2):
            for u in range(4):
                b.submit(Request(uid=u, prompt=rng.integers(
                    4, 60, size=5).astype(np.int32), max_new_tokens=5))
            done = b.run()
            assert len(done) == 4 * (generation + 1)
            assert b.allocator.available == b.num_blocks
            assert (b.tables == -1).all()

    def test_allocator_all_or_nothing(self):
        a = BlockAllocator(4)
        assert a.alloc(5) is None and a.available == 4
        got = a.alloc(3)
        assert len(got) == 3 and a.available == 1
        assert a.alloc(2) is None and a.available == 1
        a.free(got)
        assert a.available == 4
        assert sorted(a.alloc(4)) == [0, 1, 2, 3]

    def test_long_prompt_fits_blocks_but_not_dense_slot(self):
        """A 40-token prompt overflows a dense max_len=32 slot but is
        admitted by a paged pool of the SAME total memory (2 slots * 32 =
        4 blocks * 16) because max_len is only a logical cap there."""
        cfg = _tiny(max_seq_len=128)
        params = model_init(KEY, cfg)
        prompt = np.arange(4, 44, dtype=np.int32)   # 40 tokens
        dense = ContinuousBatcher(params, cfg, batch_size=2, max_len=32)
        with pytest.raises(ValueError, match="do not fit"):
            dense.submit(Request(uid=0, prompt=prompt, max_new_tokens=6))
        ref = _refs(params, cfg, [prompt], [6])[0]
        _, out = _run_batcher(params, cfg, [prompt], [6],
                              batch_size=2, max_len=64,
                              paged=True, block_size=16, num_blocks=4)
        np.testing.assert_array_equal(out[0], ref)


class TestCapacity:
    @pytest.mark.slow
    def test_2x_short_request_admission_at_equal_memory(self):
        """Acceptance: with block_size=16, a pool worth N=2 dense slots of
        max_len=512 admits >= 2x more concurrent <=64-token requests under
        the paged allocator (here: 8x)."""
        cfg = _tiny()
        params = model_init(KEY, cfg)
        n_dense_slots, max_len, block = 2, 512, 16
        num_blocks = n_dense_slots * max_len // block            # 64
        rng = np.random.default_rng(1)
        prompts = [rng.integers(4, 60, size=48).astype(np.int32)
                   for _ in range(16)]
        max_new = [16] * 16                                      # <= 64 total

        # token_budget must cover one 48-token chunk per admitted row for
        # every row to advance on the FIRST tick (the quantity this test
        # measures is pool capacity, not budget throttling)
        dense = ContinuousBatcher(params, cfg, batch_size=n_dense_slots,
                                  max_len=max_len, token_budget=1024)
        paged = ContinuousBatcher(params, cfg, batch_size=16, max_len=max_len,
                                  paged=True, block_size=block,
                                  num_blocks=num_blocks, token_budget=1024)
        for b in (dense, paged):
            for u, p in enumerate(prompts):
                b.submit(Request(uid=u, prompt=p, max_new_tokens=max_new[u]))
        dense_concurrent = dense.step()
        paged_concurrent = paged.step()
        assert dense_concurrent == n_dense_slots
        assert paged_concurrent >= 2 * dense_concurrent
        assert paged_concurrent == 16     # ceil(49/16)=4 blocks/req, 64/4=16


class TestPreemption:
    @pytest.mark.slow
    def test_pool_exhaustion_preempts_and_resumes_exactly(self):
        """Two growing requests over-commit a 6-block pool: the youngest is
        preempted (blocks freed, recompute-resume from the queue front) and
        both still produce exactly the sequential-generate tokens, with the
        pool fully reclaimed afterwards."""
        cfg, params = _setup()
        rng = np.random.default_rng(7)
        prompts = [rng.integers(4, 60, size=8).astype(np.int32)
                   for _ in range(2)]
        max_new = [12, 12]   # grows to 20 tokens = 5 blocks each; pool has 6
        refs = _refs(params, cfg, prompts, max_new)
        b, out = _run_batcher(params, cfg, prompts, max_new,
                              batch_size=2, max_len=32,
                              paged=True, block_size=4, num_blocks=6)
        for u, ref in enumerate(refs):
            np.testing.assert_array_equal(out[u], ref, err_msg=f"uid={u}")
        assert b.allocator.available == b.num_blocks
        assert (b.tables == -1).all()

    @pytest.mark.slow
    def test_preempt_with_ring_inside_window_resumes_exactly(self):
        """Preempting a mixed attn+local_attn row whose resume prefill fits
        the window must stay exact — the resume path re-prefills the ring
        from scratch like any admission."""
        cfg = _tiny(pattern=("attn", "local_attn"), window=16, max_seq_len=64)
        params = model_init(KEY, cfg)
        rng = np.random.default_rng(9)
        prompts = [rng.integers(4, 60, size=8).astype(np.int32)
                   for _ in range(2)]
        max_new = [12, 12]   # stalls at pos 12 <= window 16 -> preemptable
        refs = _refs(params, cfg, prompts, max_new)
        b, out = _run_batcher(params, cfg, prompts, max_new,
                              batch_size=2, max_len=32,
                              paged=True, block_size=4, num_blocks=6)
        for u, ref in enumerate(refs):
            np.testing.assert_array_equal(out[u], ref, err_msg=f"uid={u}")
        assert b.allocator.available == b.num_blocks

    def test_preempt_past_ring_window_resumes_exactly(self):
        """A stalled row past the local_attn window IS preemptable now:
        recompute-resume re-enters the chunked prefill path (chunks capped
        at the window), which the seed's one-shot ring prefill had to
        refuse with a RuntimeError. Both requests still produce exactly
        the cache-free oracle's tokens and the pool fully reclaims."""
        cfg = _tiny(pattern=("attn", "local_attn"), window=8, max_seq_len=64)
        params = model_init(KEY, cfg)
        rng = np.random.default_rng(9)
        prompts = [rng.integers(4, 60, size=8).astype(np.int32)
                   for _ in range(2)]
        refs = [_ref_free(params, cfg, p, 12) for p in prompts]
        b, out = _run_batcher(params, cfg, prompts, [12, 12],
                              batch_size=2, max_len=32,
                              paged=True, block_size=4, num_blocks=6)
        for u, ref in enumerate(refs):
            np.testing.assert_array_equal(out[u], ref, err_msg=f"uid={u}")
        assert b.allocator.available == b.num_blocks
        assert (b.tables == -1).all()

    def test_single_request_larger_than_pool_raises(self):
        cfg = _tiny(max_seq_len=64)
        params = model_init(KEY, cfg)
        b = ContinuousBatcher(params, cfg, batch_size=1, max_len=64,
                              paged=True, block_size=4, num_blocks=3)
        b.submit(Request(uid=0, prompt=np.arange(4, 12, dtype=np.int32),
                         max_new_tokens=20))
        with pytest.raises((RuntimeError, ValueError), match="pool"):
            b.run()
