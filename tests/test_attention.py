"""Dense vs chunked attention equivalence across the paper's softmax
variants, GQA, local windows, soft-caps, decode offsets."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.attention import AttentionConfig, chunked_attention, dense_attention
from repro.core.softmax import ClippedSoftmaxConfig

KEY = jax.random.PRNGKey(0)


def _qkv(b=2, t=96, h=8, hkv=4, d=16, tk=None):
    tk = tk or t
    ks = jax.random.split(KEY, 3)
    return (jax.random.normal(ks[0], (b, t, h, d)),
            jax.random.normal(ks[1], (b, tk, hkv, d)),
            jax.random.normal(ks[2], (b, tk, hkv, d)))


SOFTMAXES = [
    ClippedSoftmaxConfig(),
    ClippedSoftmaxConfig(gamma=-0.03),
    ClippedSoftmaxConfig(gamma=-0.01, zeta=1.03),
    ClippedSoftmaxConfig(alpha=4.0),
]


@pytest.mark.parametrize("sm", SOFTMAXES)
@pytest.mark.parametrize("window", [None, 24])
def test_dense_vs_chunked(sm, window):
    q, k, v = _qkv()
    cfg = AttentionConfig(n_heads=8, n_kv_heads=4, d_head=16, causal=True,
                          window=window, softmax=sm, chunk_size=32, seq_len=96)
    np.testing.assert_allclose(
        dense_attention(q, k, v, cfg), chunked_attention(q, k, v, cfg),
        atol=3e-5)


def test_bidirectional_and_softcap():
    q, k, v = _qkv()
    cfg = AttentionConfig(n_heads=8, n_kv_heads=4, d_head=16, causal=False,
                          logit_softcap=30.0,
                          softmax=ClippedSoftmaxConfig(gamma=-0.02),
                          chunk_size=40)
    np.testing.assert_allclose(
        dense_attention(q, k, v, cfg), chunked_attention(q, k, v, cfg),
        atol=3e-5)


def test_decode_offset_matches_full():
    """q_offset decode slice reproduces the corresponding full-attn rows."""
    q, k, v = _qkv(t=32)
    cfg = AttentionConfig(n_heads=8, n_kv_heads=4, d_head=16, causal=True,
                          softmax=ClippedSoftmaxConfig(gamma=-0.03))
    full = dense_attention(q, k, v, cfg)
    last = dense_attention(q[:, 31:32], k, v, cfg, q_offset=31)
    np.testing.assert_allclose(full[:, 31:32], last, atol=1e-5)


def test_gate_pi_scales_output():
    q, k, v = _qkv(t=16)
    cfg = AttentionConfig(n_heads=8, n_kv_heads=4, d_head=16)
    pi = jnp.full((2, 16, 8), 0.5)
    base = dense_attention(q, k, v, cfg)
    gated = dense_attention(q, k, v, cfg, gate_pi=pi)
    np.testing.assert_allclose(gated, 0.5 * base, atol=1e-6)


def test_dispatcher_routing(monkeypatch):
    """Pin `attention`'s dense/chunked routing. Regression for the
    precedence trap `... or tq == 1 and tk <= 8192` (the `and` bound
    tighter than intended reads suggested): decode with a long KV axis must
    stream, not materialize (Tq, Tk)."""
    import sys

    import repro.core.attention  # noqa: F401 — repro.core re-exports the
    A = sys.modules["repro.core.attention"]  # fn `attention`, shadowing it

    routed = []
    monkeypatch.setattr(A, "dense_attention",
                        lambda *a, **k: routed.append("dense"))
    monkeypatch.setattr(A, "chunked_attention",
                        lambda *a, **k: routed.append("chunked"))
    cfg = AttentionConfig(n_heads=1, n_kv_heads=1, d_head=4)

    def route(tq, tk, force_dense=False):
        routed.clear()
        q = jnp.zeros((1, tq, 1, 4))
        kv = jnp.zeros((1, tk, 1, 4))
        A.attention(q, kv, kv, cfg, force_dense=force_dense)
        return routed[0]

    assert route(1, 512) == "dense"          # decode, short KV
    assert route(1, 8192) == "dense"         # decode, at the dense cap
    assert route(1, 8193) == "chunked"       # decode, long KV -> stream
    assert route(64, 512) == "dense"         # small prefill
    assert route(2048, 2048) == "dense"      # at the inner dense cap
    assert route(3000, 3000) == "chunked"    # mid region streams
    assert route(8192, 8192) == "chunked"    # large prefill streams
    assert route(8192, 8192, force_dense=True) == "dense"


def test_clipped_rows_not_normalized():
    """Clipped softmax rows may sum < 1 (the no-op capability)."""
    q, k, v = _qkv(t=8)
    cfg = AttentionConfig(n_heads=8, n_kv_heads=4, d_head=16,
                          softmax=ClippedSoftmaxConfig(gamma=-0.5))
    out = dense_attention(q, k * 0 + 10.0, v, cfg)
    assert not bool(jnp.any(jnp.isnan(out)))
