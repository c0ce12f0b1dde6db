"""Plain float32 reference of a decoder of another family than OPT, for the
test that adds a configuration as files only: pre-RMSNorm blocks, rotary
positions (rotate-half), grouped-query attention whose head size is not
``hidden_size / num_attention_heads``, a SwiGLU MLP, no biases and an LM
head of its own. Keys follow a published ``config.json`` of that family.

Like every reference of the benchmark it imports nothing of the program:
``make_params`` draws every leaf from the seed in one jitted call, in the
program's parameter layout, and ``logits_fn`` is the cache-free forward at
``precision="highest"``. It has no control (``bits``): the test that uses
it runs no control.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def param_shapes(model: dict) -> dict:
    d, h = model["hidden_size"], model["num_attention_heads"]
    hkv, dh = model["num_key_value_heads"], model["head_dim"]
    ff = model["intermediate_size"]
    block = {"ln1": {"scale": (d,)}, "q": {"w": (d, h * dh)},
             "k": {"w": (d, hkv * dh)}, "v": {"w": (d, hkv * dh)},
             "o": {"w": (h * dh, d)}, "ln2": {"scale": (d,)},
             "mlp": {"gate": {"w": (d, ff)}, "up": {"w": (d, ff)},
                     "down": {"w": (ff, d)}}}
    return {"embed": {"table": (model["vocab_size"], d)},
            "layers": [{"b0": block}
                       for _ in range(model["num_hidden_layers"])],
            "final_norm": {"scale": (d,)},
            "lm_head": {"w": (d, model["vocab_size"])}}


def make_params(model: dict, init: dict, seed: int, dtype) -> dict:
    """Every weight drawn from ``seed`` on the device in one jitted call:
    norm scales around 1, q/k weights at ``qk_std``, the rest at ``std``."""
    flat, tree = jax.tree_util.tree_flatten_with_path(
        param_shapes(model), is_leaf=lambda x: isinstance(x, tuple))

    def draw(key, path, shape):
        names = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
        z = jax.random.normal(key, shape, jnp.float32)
        if names[-1] == "scale":
            return 1.0 + init["norm_std"] * z
        if names[-2] in ("q", "k"):
            return init["qk_std"] * z
        return init["std"] * z

    def build(key):
        keys = jax.random.split(key, len(flat))
        return jax.tree_util.tree_unflatten(tree, [
            draw(k, p, s).astype(dtype) for k, (p, s) in zip(keys, flat)])

    return jax.jit(build)(jax.random.PRNGKey(seed % 2**32))


def _rms(scale, x, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * scale


def _rope(x, theta):
    """Rotate-half rotary embedding of (T, H, Dh) at positions 0..T-1."""
    t, dh = x.shape[0], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _forward(params, tokens, at, model: dict):
    h, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    dh, eps = model["head_dim"], model["rms_norm_eps"]
    theta = model["rope_theta"]
    t = tokens.shape[0]
    x = params["embed"]["table"][tokens]
    causal = jnp.tril(jnp.ones((t, t), bool))
    for layer in params["layers"]:
        p = layer["b0"]
        a = _rms(p["ln1"]["scale"], x, eps)
        q = _rope((a @ p["q"]["w"]).reshape(t, h, dh), theta)
        k = _rope((a @ p["k"]["w"]).reshape(t, hkv, dh), theta)
        v = (a @ p["v"]["w"]).reshape(t, hkv, dh)
        # query head i reads KV head i // (h / hkv)
        k, v = jnp.repeat(k, h // hkv, axis=1), jnp.repeat(v, h // hkv, axis=1)
        s = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(dh)
        s = jnp.where(causal[None], s, -jnp.inf)
        o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
        x = x + o.reshape(t, h * dh) @ p["o"]["w"]
        a2 = _rms(p["ln2"]["scale"], x, eps)
        m = p["mlp"]
        g = jax.nn.silu(a2 @ m["gate"]["w"]) * (a2 @ m["up"]["w"])
        x = x + g @ m["down"]["w"]
    hf = _rms(params["final_norm"]["scale"], x, eps)[at]
    return hf @ params["lm_head"]["w"]


def logits_fn(model: dict):
    """Jitted ``(params f32, tokens (T,), at (P,)) -> logits (P, vocab)``."""
    fwd = jax.jit(lambda p, tok, at: _forward(p, tok, at, model))

    def call(params, tokens, at):
        with jax.default_matmul_precision("highest"):
            return fwd(params, tokens, at)

    return call
