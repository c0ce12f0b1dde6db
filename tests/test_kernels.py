"""Pallas kernels vs pure-jnp oracles: shape/dtype/config sweeps
(interpret mode on CPU; TPU is the target)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import fake_quant_op, linear_w8a8, mha_flash, rglru_op
from repro.kernels.flash_attention import flash_attention
from repro.kernels.int8_matmul import int8_matmul, quantize_weights_int8
from repro.kernels.ref import (
    attention_ref, fake_quant_ref, int8_matmul_ref, rglru_ref,
)

KEY = jax.random.PRNGKey(0)

pytestmark = pytest.mark.slow  # Pallas interpret-mode kernel sweeps


class TestFlashAttention:
    @pytest.mark.parametrize("shape", [(2, 128, 128, 64), (3, 96, 160, 32),
                                       (1, 33, 70, 16)])
    @pytest.mark.parametrize("variant", [
        dict(), dict(gamma=-0.03), dict(gamma=-0.01, zeta=1.03),
        dict(causal=False), dict(window=40),
        dict(softcap=30.0, gamma=-0.02), dict(q_offset=5)])
    def test_vs_oracle(self, shape, variant):
        bh, tq, tk, dh = shape
        ks = jax.random.split(KEY, 4)
        q = jax.random.normal(ks[0], (bh, tq, dh))
        k = jax.random.normal(ks[1], (bh, tk, dh))
        v = jax.random.normal(ks[2], (bh, tk, dh))
        o = flash_attention(q, k, v, None, block_q=64, block_kv=64, **variant)
        r = attention_ref(q, k, v, None, **variant)
        np.testing.assert_allclose(o, r, atol=3e-5)

    @pytest.mark.parametrize("gamma", [0.0, -0.05])
    def test_gated(self, gamma):
        bh, t, dh = 2, 64, 32
        ks = jax.random.split(KEY, 4)
        q = jax.random.normal(ks[0], (bh, t, dh))
        k = jax.random.normal(ks[1], (bh, t, dh))
        v = jax.random.normal(ks[2], (bh, t, dh))
        g = jax.nn.sigmoid(jax.random.normal(ks[3], (bh, t)))
        o = flash_attention(q, k, v, g, gamma=gamma, block_q=32, block_kv=32)
        r = attention_ref(q, k, v, g, gamma=gamma)
        np.testing.assert_allclose(o, r, atol=3e-5)

    def test_bf16(self):
        q = jax.random.normal(KEY, (2, 64, 64), jnp.bfloat16)
        o = flash_attention(q, q, q, None, gamma=-0.02)
        r = attention_ref(q, q, q, None, gamma=-0.02)
        assert o.dtype == jnp.bfloat16
        np.testing.assert_allclose(o.astype(jnp.float32),
                                   r.astype(jnp.float32), atol=2e-2)

    def test_gqa_adapter_vs_core(self):
        from repro.core.attention import AttentionConfig, dense_attention
        from repro.core.softmax import ClippedSoftmaxConfig
        B, T, H, HKV, D = 2, 64, 8, 4, 32
        ks = jax.random.split(KEY, 4)
        q = jax.random.normal(ks[0], (B, T, H, D))
        k = jax.random.normal(ks[1], (B, T, HKV, D))
        v = jax.random.normal(ks[2], (B, T, HKV, D))
        gate = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H)))
        cfg = AttentionConfig(n_heads=H, n_kv_heads=HKV, d_head=D,
                              softmax=ClippedSoftmaxConfig(gamma=-0.03))
        o = mha_flash(q, k, v, gate, gamma=-0.03, block_q=32, block_kv=32)
        r = dense_attention(q, k, v, cfg, gate_pi=gate)
        np.testing.assert_allclose(o, r, atol=3e-5)


class TestInt8Matmul:
    @pytest.mark.parametrize("shape", [(128, 128, 128), (100, 70, 36),
                                       (256, 512, 384), (64, 1000, 200)])
    def test_vs_oracle(self, shape):
        m, k, n = shape
        x = jax.random.normal(KEY, (m, k)) * 2
        w = jax.random.normal(jax.random.PRNGKey(1), (k, n)) * 0.1
        wq, ws = quantize_weights_int8(w)
        o = int8_matmul(x, wq, ws, block_m=64, block_n=64, block_k=64)
        r = int8_matmul_ref(x, wq, ws)
        np.testing.assert_allclose(o, r, atol=1e-3, rtol=1e-4)

    @pytest.mark.parametrize("static", [False, True])
    def test_one_sided_range_keeps_all_codes(self, static):
        """A ReLU output has zero-point 0 and codes up to 255: the kernel
        must fold the zero-point out exactly, not saturate x_q - z to int8
        (which would clip the upper half of the range)."""
        x = jax.nn.relu(jax.random.normal(KEY, (64, 256)))
        w = jax.random.normal(jax.random.PRNGKey(1), (256, 128)) * 0.05
        wq, ws = quantize_weights_int8(w)
        s = float(jnp.max(x)) / 255.0
        kw = dict(x_scale=s, x_zero=0.0) if static else {}
        o = int8_matmul(x, wq, ws, block_m=64, block_n=64, block_k=64, **kw)
        np.testing.assert_allclose(o, int8_matmul_ref(x, wq, ws),
                                   atol=1e-3, rtol=1e-4)
        f = x @ w
        rel = float(jnp.mean(jnp.abs(o - f)) / jnp.mean(jnp.abs(f)))
        assert rel < 0.03, rel

    def test_quality_vs_float(self):
        """W8A8 of outlier-free activations is within ~2%% of fp matmul —
        the regime the paper's method creates."""
        x = jax.random.normal(KEY, (128, 256))
        w = jax.random.normal(jax.random.PRNGKey(1), (256, 128)) * 0.05
        wq, ws = quantize_weights_int8(w)
        o = linear_w8a8(x, wq, ws)
        f = x @ w
        rel = float(jnp.mean(jnp.abs(o - f)) / jnp.mean(jnp.abs(f)))
        assert rel < 0.03

    def test_outliers_destroy_w8a8(self):
        """With a BERT-like outlier the per-tensor range collapses — the
        failure mode the paper fixes at the architecture level."""
        x = jax.random.normal(KEY, (128, 256))
        x_out = x.at[0, 0].set(500.0)
        w = jax.random.normal(jax.random.PRNGKey(1), (256, 128)) * 0.05
        wq, ws = quantize_weights_int8(w)
        f = x_out @ w
        o = linear_w8a8(x_out, wq, ws)
        rel = float(jnp.mean(jnp.abs(o - f)) / jnp.mean(jnp.abs(f)))
        assert rel > 0.2   # catastrophic vs the 0.03 above


class TestFakeQuantKernel:
    @pytest.mark.parametrize("n", [1000, 4096, 777])
    @pytest.mark.parametrize("bits", [4, 8])
    def test_vs_oracle(self, n, bits):
        x = jax.random.normal(KEY, (n,)) * 3
        s, z = 0.05, 2.0 ** (bits - 1)
        np.testing.assert_allclose(
            fake_quant_op(x, s, z, bits), fake_quant_ref(x, s, z, bits),
            atol=1e-6)


class TestRGLRUKernel:
    @pytest.mark.parametrize("shape", [(2, 37, 24), (1, 128, 512), (3, 8, 700)])
    def test_vs_oracle(self, shape):
        b, t, d = shape
        a = jax.nn.sigmoid(jax.random.normal(KEY, shape))
        bb = jax.random.normal(jax.random.PRNGKey(1), shape)
        h, hl = rglru_op(a, bb)
        hr, hlr = rglru_ref(a, bb)
        np.testing.assert_allclose(h, hr, atol=1e-5)
        np.testing.assert_allclose(hl, hlr, atol=1e-5)

    def test_state_carry(self):
        a = jax.nn.sigmoid(jax.random.normal(KEY, (2, 16, 8)))
        b = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 8))
        h_full, _ = rglru_ref(a, b)
        h1, hl1 = rglru_op(a[:, :9], b[:, :9])
        h2, _ = rglru_op(a[:, 9:], b[:, 9:], h0=hl1)
        np.testing.assert_allclose(
            jnp.concatenate([h1, h2], axis=1), h_full, atol=1e-5)
