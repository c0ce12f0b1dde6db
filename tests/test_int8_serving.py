"""Hardware-path int8 serving: the int8 weight cache and linear pieces,
the quantized paged KV pool (per-slot scale roundtrip, partial tail
blocks, equal-memory admission capacity), and the W8A8 engine tick's
compile-count guard. Token-level quality/agreement lives in
test_int8_serving_quality.py; the fp scheduler itself is covered by
test_serving_engine.py / test_chunked_prefill.py."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_models import opt_tiny
from repro.models import model_init
from repro.models.transformer import (
    init_paged_cache,
    model_apply,
    paged_kv_block_bytes,
)
from repro.quant import QConfig, kv_dequant, kv_quant
from repro.quant.int8_weights import build_int8_cache, int8_cache_bytes, linear_int8
from repro.serving.scheduler import ContinuousBatcher, Request

KEY = jax.random.PRNGKey(0)


class TestInt8WeightCache:
    def test_cache_covers_matmuls_and_skips_head(self):
        cfg = opt_tiny(vocab=128, seq_len=32)
        params = model_init(KEY, cfg)
        cache = build_int8_cache(params)
        assert any("/q/w" in p for p in cache)
        assert any("/mlp/up/w" in p for p in cache)
        assert not any("lm_head" in p for p in cache)
        # int8 cache is ~4x smaller than f32 weights it replaces
        f32_bytes = sum(
            np.prod(np.asarray(v[0].shape)) * 4 for v in cache.values())
        assert int8_cache_bytes(cache) * 3.9 < f32_bytes

    def test_int8_linear_matches_float_within_quant_error(self):
        cfg = opt_tiny(vocab=128, seq_len=32)
        params = model_init(KEY, cfg)
        cache = build_int8_cache(params)
        path = next(p for p in cache if p.endswith("/q/w"))
        # locate the float weight
        from repro.nn.module import flatten_params
        w = dict(flatten_params(params))[path]
        x = jax.random.normal(KEY, (4, 8, w.shape[0]))
        y_int8 = linear_int8(cache, path, x)
        y_fp = x @ w
        rel = float(jnp.mean(jnp.abs(y_int8 - y_fp)) / jnp.mean(jnp.abs(y_fp)))
        assert rel < 0.05, rel


class TestContinuousBatcher:
    def _setup(self, B=3):
        cfg = dataclasses.replace(opt_tiny(vocab=64, seq_len=32),
                                  max_seq_len=64)
        params = model_init(KEY, cfg)
        return ContinuousBatcher(params, cfg, batch_size=B, max_len=64)

    def test_all_requests_complete(self):
        b = self._setup()
        rng = np.random.default_rng(0)
        reqs = [Request(uid=i, prompt=rng.integers(4, 64, size=5).astype(np.int32),
                        max_new_tokens=6) for i in range(5)]
        for r in reqs:
            b.submit(r)
        done = b.run()
        assert len(done) == 5
        for r in done:
            assert r.output is not None and len(r.output) == 6

    def test_outputs_match_unbatched_decode(self):
        """A scheduled request decodes the same tokens as a dedicated
        single-sequence generate (cache-row isolation)."""
        from repro.serving import GenerateConfig, generate
        b = self._setup(B=2)
        prompt = np.arange(4, 10, dtype=np.int32)
        b.submit(Request(uid=0, prompt=prompt, max_new_tokens=5))
        b.submit(Request(uid=1, prompt=prompt[::-1].copy(), max_new_tokens=5))
        done = sorted(b.run(), key=lambda r: r.uid)
        ref = generate(b.params, b.cfg, jnp.asarray(prompt)[None, :],
                       GenerateConfig(max_new_tokens=5))
        np.testing.assert_array_equal(done[0].output,
                                      np.asarray(ref[0, len(prompt):]))

    def test_slots_refill_from_queue(self):
        b = self._setup(B=2)
        rng = np.random.default_rng(1)
        for i in range(4):   # 4 requests through 2 slots
            b.submit(Request(uid=i,
                             prompt=rng.integers(4, 64, 4).astype(np.int32),
                             max_new_tokens=3))
        done = b.run()
        assert len(done) == 4


def _small_cfg(**kw):
    base = dataclasses.replace(opt_tiny(vocab=64, seq_len=32), n_layers=2,
                               d_model=64, n_heads=2, n_kv_heads=2, d_head=32,
                               d_ff=256, max_seq_len=64)
    return dataclasses.replace(base, **kw)


class TestInt8KVPool:
    """The quantized paged KV pool in isolation: per-slot scale roundtrip
    and the fused quantize-on-scatter against the fp pool oracle."""

    def test_roundtrip_error_bounded_by_half_step(self):
        """Property (seeded sweep over magnitudes 1e-4..1e3): dequant(
        quant(x)) is within half a quantization step of x per (block,
        slot), and the stored scale is exactly amax/127 (clamped)."""
        rng = np.random.default_rng(0)
        for i in range(20):
            mag = 10.0 ** rng.uniform(-4, 3)
            x = (rng.standard_normal((5, 8, 2, 16)) * mag).astype(np.float32)
            q, s = kv_quant(jnp.asarray(x))
            assert q.dtype == jnp.int8 and s.shape == (5, 8)
            amax = np.abs(x).max(axis=(-2, -1))
            np.testing.assert_allclose(np.asarray(s),
                                       np.maximum(amax / 127.0, 1e-8),
                                       rtol=1e-6, err_msg=f"iter {i}")
            err = np.abs(np.asarray(kv_dequant(q, s)) - x)
            half_step = np.asarray(s)[..., None, None] * 0.5
            assert np.all(err <= half_step + 1e-7 * mag), f"iter {i}"

    def test_zero_slots_keep_eps_scale(self):
        q, s = kv_quant(jnp.zeros((2, 4, 2, 8)))
        assert not np.asarray(q).any()
        np.testing.assert_allclose(np.asarray(s), 1e-8, rtol=1e-6)
        assert not np.asarray(kv_dequant(q, s)).any()

    def test_partial_tail_block_scales(self):
        """Write 5 tokens into an 8-slot block through the model's masked
        scatter (scrambled physical table): written slots dequantize to
        the fp pool within half a step, their scales are per-TOKEN amax
        (not a block-wide max), and unwritten slots keep zero codes and
        zero scales."""
        cfg = _small_cfg(max_seq_len=16)
        params = model_init(KEY, cfg)
        tokens = jnp.asarray([[5, 9, 17, 33, 2]], jnp.int32)
        table = jnp.asarray([[2, 0]], jnp.int32)

        def run(kv_int8):
            cache = init_paged_cache(cfg, 1, 16, num_blocks=3, block_size=8,
                                     kv_int8=kv_int8)

            def set_table(path, leaf):
                if path and path[-1] == jax.tree_util.DictKey("block_table"):
                    return jnp.broadcast_to(table, leaf.shape)
                return leaf

            cache = jax.tree_util.tree_map_with_path(set_table, cache)
            _, aux = model_apply(params, cfg, {"tokens": tokens}, cache=cache,
                                 pos=jnp.asarray([0], jnp.int32),
                                 active=jnp.ones((1, 5), bool))
            return {jax.tree_util.keystr(p): np.asarray(leaf) for p, leaf
                    in jax.tree_util.tree_leaves_with_path(aux["cache"])}

        fp, i8 = run(False), run(True)
        scale_paths = [p for p in i8 if p.endswith("'k_scale']")
                       or p.endswith("'v_scale']")]
        assert scale_paths, "no int8 attn pools in the cache"
        tight_checked = 0
        # token-major views of the head-major pools: (NB, BS, Hkv, Dh)
        # codes and (NB, BS) scales
        def slots(pool):
            return pool.transpose(0, 2, 1, 3)

        for sp in scale_paths:
            pool_p = sp.replace("_scale", "")
            q, s = slots(i8[pool_p]), i8[sp][:, 0]
            assert q.dtype == np.int8
            # tail slots of the written block + both unwritten blocks
            assert not q[2, 5:].any() and not s[2, 5:].any(), sp
            assert not q[[0, 1]].any() and not s[[0, 1]].any(), sp
            if "'layers'][0" not in sp:
                # deeper layers see inputs already perturbed by layer 0's
                # KV dequant, so the fp pool is no longer a tight oracle
                continue
            ref = slots(fp[pool_p])
            # tokens 0..4 land in physical block 2 (table[0] == 2)
            got = q[2, :5].astype(np.float32) * s[2, :5, None, None]
            err = np.abs(got - ref[2, :5])
            assert np.all(err <= s[2, :5, None, None] * 0.5 + 1e-7), sp
            amax = np.abs(ref[2, :5]).max(axis=(-2, -1))
            np.testing.assert_allclose(s[2, :5], np.maximum(amax / 127, 1e-8),
                                       rtol=1e-5, err_msg=sp)
            tight_checked += 1
        assert tight_checked == 2    # layer 0's k_scale and v_scale


class TestInt8KVCapacity:
    """ROADMAP item #1's capacity claim, measured with the same byte
    accounting the pools allocate (paged_kv_block_bytes): at equal pool
    memory the int8 engine concurrently advances ~3x the rows of fp
    (asserted >= 1.8x; f32 pools shrink ~3.5x, bf16 ~2x)."""

    @pytest.mark.compile_budget(24)
    def test_equal_memory_admits_2x_rows(self):
        cfg = _small_cfg()
        params = model_init(KEY, cfg)
        bs = 8
        budget = 12 * paged_kv_block_bytes(cfg, bs, kv_int8=False)
        rng = np.random.default_rng(0)
        prompts = [rng.integers(4, 64, 25).astype(np.int32) for _ in range(8)]

        def peak_rows(kv_int8):
            nb = budget // paged_kv_block_bytes(cfg, bs, kv_int8=kv_int8)
            b = ContinuousBatcher(params, cfg, batch_size=8, max_len=32,
                                  paged=True, block_size=bs, num_blocks=nb,
                                  kv_int8=kv_int8)
            for u, p in enumerate(prompts):
                b.submit(Request(uid=u, prompt=p, max_new_tokens=2))
            peak = ticks = 0
            while (b.queue or any(s.req is not None for s in b.slots)) \
                    and ticks < 500:
                b.step()
                ticks += 1
                peak = max(peak, sum(1 for s in b.slots if s.blocks))
            assert len(b.done) == 8
            return peak, nb

        peak_fp, nb_fp = peak_rows(False)
        peak_i8, nb_i8 = peak_rows(True)
        # each row needs 4 blocks (25-token prompt + 2 decodes, block 8)
        assert peak_fp == nb_fp // 4, (peak_fp, nb_fp)
        assert nb_i8 >= 1.8 * nb_fp, (nb_i8, nb_fp)
        assert peak_i8 >= 1.8 * peak_fp, (peak_i8, peak_fp)
        assert peak_i8 == 8          # the whole batch fits at equal memory


class TestInt8EngineTick:
    """The W8A8 + int8-KV tick is guarded against jit-specialization
    explosions exactly like the fp tick (test_compile_guard)."""

    @pytest.mark.compile_budget(10)
    def test_int8_tick_sweep_within_pow2_budget(self):
        """Decode across several pow-2 live-width boundaries on the full
        int8 engine: calibration + weight quantization happen once at
        construction (eager, zero tracked compiles), and the tick takes at
        most one variant per (phase, pow-2 bucket) — the same budget shape
        as the fp sweep in test_compile_guard."""
        cfg = _small_cfg()
        params = model_init(KEY, cfg)
        b = ContinuousBatcher(params, cfg, batch_size=1, max_len=32,
                              paged=True, block_size=2, num_blocks=20,
                              qconfig=QConfig())
        assert b.kv_int8    # defaults on for a paged qconfig engine
        b.submit(Request(uid=0, prompt=np.arange(2, 4, dtype=np.int32),
                         max_new_tokens=25))
        out = b.run()[0].output
        assert out.shape == (25,)
