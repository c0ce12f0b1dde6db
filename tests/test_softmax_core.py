"""Unit + property tests for the paper's core math (clipped softmax,
gating, outlier metrics)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.softmax import (
    ClippedSoftmaxConfig, clipped_softmax, softcap, softmax, stretch_and_clip,
)
from repro.core.gating import GateConfig, gate_param_count, gate_probs, init_gate
from repro.core.outliers import (
    infinity_norm, kurtosis, outlier_counts_by_dim, outlier_mask,
)

KEY = jax.random.PRNGKey(0)


class TestClippedSoftmax:
    def test_vanilla_equivalence_at_gamma0(self):
        x = jax.random.normal(KEY, (4, 32))
        np.testing.assert_allclose(
            clipped_softmax(x, gamma=0.0, zeta=1.0), softmax(x), atol=1e-7)

    def test_exact_zeros_with_finite_range(self):
        """The paper's central claim: gamma < 0 makes exact zeros reachable
        with a FINITE logit range (Eq. 2 shows vanilla softmax cannot)."""
        x = jnp.array([[0.0, 1.0, 6.0, 6.0]])
        p = clipped_softmax(x, gamma=-0.03)
        assert p[0, 0] == 0.0 and p[0, 1] == 0.0
        assert softmax(x)[0, 0] > 0.0  # vanilla can't represent the zero

    def test_exact_ones_with_zeta(self):
        x = jnp.array([[10.0, 0.0, 0.0, 0.0]])
        p = clipped_softmax(x, gamma=0.0, zeta=1.1)
        assert p[0, 0] == 1.0

    def test_clipped_entries_get_zero_gradient(self):
        """Clipping stops the gradient that grows outliers (paper Sec 4.1)."""
        x = jnp.array([0.0, 1.0, 8.0, 8.0])
        g = jax.grad(lambda t: clipped_softmax(t, gamma=-0.03)[0])(x)
        np.testing.assert_allclose(g, jnp.zeros_like(g), atol=1e-9)
        g_v = jax.grad(lambda t: softmax(t)[0])(x)
        assert float(jnp.max(jnp.abs(g_v))) > 0  # vanilla keeps pushing

    def test_gamma_from_alpha(self):
        cfg = ClippedSoftmaxConfig(alpha=4.0)
        assert cfg.resolve_gamma(128) == pytest.approx(-4.0 / 128)
        assert not cfg.is_vanilla

    @pytest.mark.parametrize("backend", ["gather", "kernel"])
    def test_gamma_fixed_by_model_length(self, monkeypatch, backend):
        """gamma = -alpha / train_seq_len on every attention path, whatever
        KV axis it reads: opt_tiny(seq_len=64) (max_seq_len 256) clips at
        -4/64 in a training forward at T=64, a cache-free forward of 8
        tokens, and dense and paged decode against 128-token caches; an
        alpha config with no training length is refused."""
        import dataclasses
        import importlib
        from repro.configs import apply_method
        from repro.configs.paper_models import opt_tiny
        from repro.kernels import paged_attention
        from repro.models.transformer import (init_cache, init_paged_cache,
                                              model_apply, model_init)
        # the module, not the `attention` function repro.core exports
        attention = importlib.import_module("repro.core.attention")
        cfg = apply_method(opt_tiny(vocab=64, seq_len=64), "clipped_softmax",
                           alpha=4.0)
        cfg = dataclasses.replace(cfg, n_layers=1, paged_backend=backend)
        assert cfg.max_seq_len == 256
        seen = []

        def clip_spy(p, gamma, zeta):
            seen.append(gamma)
            return stretch_and_clip(p, gamma, zeta)

        def mha_spy(*a, gamma, **kw):
            seen.append(gamma)
            return real_mha(*a, gamma=gamma, interpret=True,
                            **{k: v for k, v in kw.items() if k != "interpret"})

        real_mha = paged_attention.paged_mha
        monkeypatch.setattr(attention, "stretch_and_clip", clip_spy)
        monkeypatch.setattr(paged_attention, "paged_mha", mha_spy)
        params = model_init(KEY, cfg)
        one = {"tokens": jnp.ones((1, 1), jnp.int32)}
        paged = init_paged_cache(cfg, 1, 128, num_blocks=8)
        for layer in paged["layers"]:
            layer["b0"]["block_table"] = jnp.arange(8, dtype=jnp.int32)[None]
        runs = {
            "train": lambda: model_apply(
                params, cfg, {"tokens": jnp.ones((2, 64), jnp.int32)}),
            "no cache": lambda: model_apply(
                params, cfg, {"tokens": jnp.ones((1, 8), jnp.int32)}),
            "dense decode": lambda: model_apply(
                params, cfg, one, cache=init_cache(cfg, 1, 128), pos=5),
            "paged decode": lambda: model_apply(params, cfg, one,
                                                cache=paged, pos=5),
        }
        for name, run in runs.items():
            seen.clear()
            jax.eval_shape(run)
            assert seen and all(g == pytest.approx(-4.0 / 64) for g in seen), \
                (name, seen)
        with pytest.raises(ValueError, match="trained at"):
            apply_method(dataclasses.replace(opt_tiny(), train_seq_len=None),
                         "clipped_softmax", alpha=4.0)
        with pytest.raises(ValueError, match="trained at"):
            attention.AttentionConfig(
                n_heads=4, n_kv_heads=4, d_head=32,
                softmax=ClippedSoftmaxConfig(alpha=4.0)).gamma

    def test_masked_positions_stay_zero(self):
        x = jax.random.normal(KEY, (2, 8))
        where = jnp.arange(8) < 5
        p = stretch_and_clip(softmax(x, where=where), -0.05, 1.0)
        assert float(jnp.max(jnp.abs(p[:, 5:]))) == 0.0

    @given(gamma=st.floats(-0.2, 0.0), zeta=st.floats(1.0, 1.2),
           seed=st.integers(0, 2 ** 16))
    @settings(max_examples=30, deadline=None)
    def test_range_property(self, gamma, zeta, seed):
        """Output always in [0, 1]; monotone in the input logit."""
        x = jax.random.normal(jax.random.PRNGKey(seed), (3, 16)) * 5
        p = clipped_softmax(x, gamma=gamma, zeta=zeta)
        assert float(jnp.min(p)) >= 0.0 and float(jnp.max(p)) <= 1.0

    @given(cap=st.floats(1.0, 100.0), seed=st.integers(0, 1000))
    @settings(max_examples=20, deadline=None)
    def test_softcap_bounds(self, cap, seed):
        x = jax.random.normal(jax.random.PRNGKey(seed), (16,)) * 1000
        y = softcap(x, cap)
        assert float(jnp.max(jnp.abs(y))) <= cap * 1.0001


class TestGating:
    @pytest.mark.parametrize("kind", ["linear", "mlp", "all_heads_linear"])
    def test_shapes_and_range(self, kind):
        h, dh, dm, b, t = 4, 16, 64, 2, 8
        cfg = GateConfig(kind=kind, n_hid=4)
        p = init_gate(KEY, cfg, h, dh, dm)
        xh = jax.random.normal(KEY, (b, t, h, dh))
        xm = xh.reshape(b, t, dm)
        pi = gate_probs(p, cfg, xh, xm)
        assert pi.shape == (b, t, h)
        assert float(pi.min()) >= 0.0 and float(pi.max()) <= 1.0

    def test_pi_init_controls_initial_gate(self):
        """Paper Sec 5.3: bias init sets the initial gate probability."""
        for pi_target in (0.1, 0.5, 0.9):
            cfg = GateConfig.from_pi_init(pi_target)
            p = init_gate(KEY, cfg, 4, 16, 64)
            p = jax.tree_util.tree_map(
                lambda a: jnp.zeros_like(a) if a.ndim > 1 else a, p)
            xh = jax.random.normal(KEY, (1, 4, 4, 16))
            pi = gate_probs(p, cfg, xh, xh.reshape(1, 4, 64))
            np.testing.assert_allclose(pi, pi_target, atol=1e-5)

    def test_param_count_matches_table4(self):
        """BERT-base linear gate: n_heads*(d_head+1) = 12*65 = 780 params,
        <0.009%% of 109M (paper footnote 6)."""
        assert gate_param_count(GateConfig("linear"), 12, 64, 768) == 780
        assert gate_param_count(GateConfig("mlp", n_hid=4), 12, 64, 768) \
            == 12 * (4 * 66 + 1)
        assert gate_param_count(GateConfig("all_heads_linear"), 12, 64, 768) \
            == 12 * 769

    def test_finetuning_scale(self):
        """App B.6: output_scale=2 with b_init=0 gives expected gate 1."""
        cfg = GateConfig(kind="linear", b_init=0.0, output_scale=2.0)
        p = init_gate(KEY, cfg, 2, 8, 16)
        p = jax.tree_util.tree_map(
            lambda a: jnp.zeros_like(a) if a.ndim > 1 else a, p)
        xh = jax.random.normal(KEY, (1, 3, 2, 8))
        pi = gate_probs(p, cfg, xh, xh.reshape(1, 3, 16))
        np.testing.assert_allclose(pi, 1.0, atol=1e-6)


class TestOutlierMetrics:
    def test_inf_norm(self):
        x = jnp.array([[1.0, -7.5], [2.0, 3.0]])
        assert float(infinity_norm(x)) == 7.5

    def test_kurtosis_gaussian_vs_outliers(self):
        x = jax.random.normal(KEY, (10000,))
        k_g = float(kurtosis(x))
        assert 2.5 < k_g < 3.5           # gaussian ~ 3
        x_out = x.at[0].set(100.0)
        assert float(kurtosis(x_out)) > 100.0

    def test_outlier_counts_localized(self):
        x = jax.random.normal(KEY, (4, 16, 32)) * 0.1
        x = x.at[:, 3, 7].set(50.0)   # sparse spike in one hidden dim
        counts = outlier_counts_by_dim(x, n_sigma=6.0)
        assert int(counts[7]) == 4
        assert int(counts.sum()) == 4

    @given(seed=st.integers(0, 1000), scale=st.floats(0.1, 10.0))
    @settings(max_examples=20, deadline=None)
    def test_outlier_mask_scale_invariant(self, seed, scale):
        x = jax.random.normal(jax.random.PRNGKey(seed), (256,))
        m1 = outlier_mask(x, 6.0)
        m2 = outlier_mask(x * scale, 6.0)
        assert bool(jnp.all(m1 == m2))
