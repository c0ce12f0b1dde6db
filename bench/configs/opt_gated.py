"""Plain reference of OPT with the paper's gated attention, and the
benchmark's own weights for it.

This file is the yardstick for the ``opt-125m-gated-*`` configurations. It
imports nothing of the program under test. It follows the published OPT
decoder (pre-LayerNorm blocks, learned absolute positions, ReLU MLP, biases
everywhere, LM head tied to the token embedding) with the gate of
"Quantizable Transformers" (Bondarenko et al., 2023, Eq. 5): each head's
attention output is scaled by ``sigmoid(x_h . w_h + b_h)``, where ``x_h`` is
that head's slice of the block's LayerNorm output. Departures from the
published model, all of them layout and none of them mathematics, are listed
in the configuration files (``departures``).

``make_params`` draws every leaf from the seed in one jitted call, in the
program's parameter layout and in the dtype the configuration serves.
``logits_at`` is the float32 forward, computed with
``precision="highest"``; with ``bits`` it is the control: every matmul input
and weight (not the LM head), and the cached K and V, fake-quantized to that
many bits.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

LN_EPS = 1e-5          # torch.nn.LayerNorm's default, which OPT uses


def seed_key(seed: int) -> jax.Array:
    """A PRNG key that depends on every bit of a whole-number seed."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    rest = seed >> 32
    while rest:
        key = jax.random.fold_in(key, rest & 0xFFFFFFFF)
        rest >>= 32
    return key


def param_shapes(model: dict) -> dict:
    """The program's parameter layout for this model: leaf -> shape."""
    d, h = model["hidden_size"], model["num_attention_heads"]
    dh, ff = d // h, model["ffn_dim"]

    def lin(k, n):
        return {"w": (k, n), "b": (n,)}

    def ln():
        return {"scale": (d,), "bias": (d,)}

    block = {"ln1": ln(), "q": lin(d, d), "k": lin(d, d), "v": lin(d, d),
             "o": lin(d, d), "gate": {"w": (h, dh), "b": (h,)}, "ln2": ln(),
             "mlp": {"up": lin(d, ff), "down": lin(ff, d)}}
    return {
        "embed": {"table": (model["vocab_size"], d)},
        "pos_embed": {"table": (model["max_position_embeddings"], d)},
        "layers": [{"b0": block} for _ in range(model["num_hidden_layers"])],
        "final_norm": ln(),
    }


def _draw(key, path, shape, init: dict):
    """One leaf's values: LayerNorm scales around 1, gate weights at the
    He scale of the head width, q/k weights at ``qk_std`` (so attention is
    far from uniform and the check sees which keys it read), every other
    weight, embedding and bias at ``std``."""
    name = "/".join(path)
    z = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("scale"):
        return 1.0 + init["norm_std"] * z
    if name.endswith("bias"):
        return init["norm_std"] * z
    if "gate" in name:
        std = math.sqrt(2.0 / shape[-1]) if name.endswith("w") \
            else init["gate_bias_std"]
        return std * z
    if name.split("/")[-2] in ("q", "k") and name.endswith("w"):
        return init["qk_std"] * z
    return init["std"] * z


def make_params(model: dict, init: dict, seed: int, dtype) -> dict:
    """Every weight of the model, drawn from ``seed`` on the device in one
    jitted call, in ``dtype``."""
    shapes = param_shapes(model)
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))

    def names(path):
        return tuple(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path)

    def build(key):
        keys = jax.random.split(key, len(flat))
        leaves = [_draw(k, names(p), s, init).astype(dtype)
                  for k, (p, s) in zip(keys, flat)]
        return jax.tree_util.tree_unflatten(tree, leaves)

    return jax.jit(build)(seed_key(seed))


# ---------------------------------------------------------------- reference
def _fq_sym(x, bits, axis=None):
    q = 2 ** (bits - 1) - 1
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
                    / q, 1e-8)
    return jnp.clip(jnp.round(x / s), -q, q) * s


def _fq_asym(x, bits, valid):
    """Per-tensor asymmetric fake-quant, the range taken over the real
    (``valid``) rows only."""
    top = 2 ** bits - 1
    lo = jnp.minimum(jnp.min(jnp.where(valid, x, 0.0)), 0.0)
    hi = jnp.maximum(jnp.max(jnp.where(valid, x, 0.0)), 0.0)
    s = jnp.maximum((hi - lo) / top, 1e-8)
    z = jnp.round(-lo / s)
    return (jnp.clip(jnp.round(x / s) + z, 0, top) - z) * s


def _linear(p, x, bits, valid):
    w = p["w"]
    if bits:
        x, w = _fq_asym(x, bits, valid), _fq_sym(w, bits)
    return x @ w + p["b"]


def _ln(p, x):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _forward(params, tokens, at, n_heads: int, bits: Optional[int]):
    t = tokens.shape[0]
    # rows past the last compared position are padding
    valid = (jnp.arange(t) <= jnp.max(at))[:, None]
    x = params["embed"]["table"][tokens] + params["pos_embed"]["table"][:t]
    d = x.shape[-1]
    dh = d // n_heads
    causal = jnp.tril(jnp.ones((t, t), bool))
    for layer in params["layers"]:
        p = layer["b0"]
        h = _ln(p["ln1"], x)
        q = _linear(p["q"], h, bits, valid).reshape(t, n_heads, dh)
        k = _linear(p["k"], h, bits, valid).reshape(t, n_heads, dh)
        v = _linear(p["v"], h, bits, valid).reshape(t, n_heads, dh)
        if bits:
            k = _fq_sym(k, bits, axis=(1, 2))
            v = _fq_sym(v, bits, axis=(1, 2))
        s = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(dh)
        s = jnp.where(causal[None], s, -jnp.inf)
        o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
        g = jnp.einsum("thd,hd->th", h.reshape(t, n_heads, dh),
                       p["gate"]["w"]) + p["gate"]["b"]
        o = (o * jax.nn.sigmoid(g)[..., None]).reshape(t, d)
        x = x + _linear(p["o"], o, bits, valid)
        h2 = _ln(p["ln2"], x)
        x = x + _linear(p["mlp"]["down"], jax.nn.relu(
            _linear(p["mlp"]["up"], h2, bits, valid)), bits, valid)
    hf = _ln(params["final_norm"], x)[at]
    return hf @ params["embed"]["table"].T


def logits_fn(model: dict, bits: Optional[int] = None):
    """Jitted ``(params f32, tokens (T,), at (P,)) -> logits (P, vocab)``:
    the cache-free forward over one sequence, logits at positions ``at``."""
    fwd = jax.jit(lambda p, tok, at: _forward(
        p, tok, at, model["num_attention_heads"], bits))

    def call(params, tokens, at):
        with jax.default_matmul_precision("highest"):
            return fwd(params, tokens, at)

    return call
