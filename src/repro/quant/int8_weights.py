"""Hardware-path W8A8 serving: convert calibrated FP params into an int8
weight cache and run linears through the Pallas MXU kernel.

``fake_quant`` (quant/quantizer.py) *simulates* integer inference in float —
that is the paper's evaluation protocol. This module is the deployment
counterpart: weights are stored as actual int8 (+ per-tensor scale),
activations are quantized on the fly inside the kernel, and matmuls run
int8 x int8 -> int32 (repro.kernels.int8_matmul). The two paths agree to
rounding (tests/test_int8_serving.py) — agreement is only possible because
the paper's methods removed the activation outliers.
"""
from __future__ import annotations

import re
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.int8_matmul import int8_matmul, quantize_weights_int8
from repro.nn.module import flatten_params

Array = jax.Array

# param paths worth int8-caching: the big matmul weights
_MATMUL_W = re.compile(
    r".*/(q|k|v|o|up|gate|down|in_x|in_gate|out|w_a|w_x|zifo|ff_up|ff_gate|"
    r"ff_down)/w$|.*lm_head/w$|.*embed/table$")


def build_int8_cache(params: Any, skip: Tuple[str, ...] = (r".*lm_head.*",)
                     ) -> Dict[str, Tuple[Array, Array]]:
    """Quantize every matmul weight to (int8 tensor, f32 scale)."""
    cache: Dict[str, Tuple[Array, Array]] = {}
    for path, leaf in flatten_params(params):
        if leaf.ndim != 2 or not _MATMUL_W.match(path):
            continue
        if any(re.match(p, path) for p in skip):
            continue
        wq, s = quantize_weights_int8(leaf)
        cache[path] = (wq, s)
    return cache


def int8_cache_bytes(cache: Dict[str, Tuple[Array, Array]]) -> int:
    return sum(int(wq.size) for wq, _ in cache.values())


def attach_int8_weights(params: Any, skip: Tuple[str, ...] = (r".*lm_head.*",)
                        ) -> Any:
    """Return a params tree with ``w_q8``/``w_scale`` leaves attached beside
    every matmul weight ``w``.

    Attaching to the tree (rather than a side table keyed by site name) is
    what makes the serving W8A8 path correct for every layer: site names in
    ``models.transformer.group_apply`` repeat across groups
    (``layer_attn0`` in every group), so a name-keyed cache would collide,
    while params paths are unique. It also composes with scanned configs:
    a stacked ``(G, K, N)`` weight gets a stacked ``(G, K, N)`` int8 leaf +
    ``(G,)`` per-layer scales, and the unrolled apply's ``tree_slice``
    carves out each layer's pair alongside its fp weight. ``linear_apply``
    routes through the integer kernel whenever the ctx is in 'int8' mode
    and ``w_q8`` is present."""
    def walk(node: Any, prefix: str) -> Any:
        if isinstance(node, (list, tuple)):
            return [walk(v, f"{prefix}/{i}") for i, v in enumerate(node)]
        if not isinstance(node, dict):
            return node
        out = {k: walk(v, f"{prefix}/{k}" if prefix else k)
               for k, v in node.items()}
        w = node.get("w")
        wpath = f"{prefix}/w" if prefix else "w"
        if (w is not None and not isinstance(w, (dict, list, tuple))
                and getattr(w, "ndim", 0) in (2, 3)
                and _MATMUL_W.match(wpath)
                and not any(re.match(p, wpath) for p in skip)):
            if w.ndim == 2:
                wq, s = quantize_weights_int8(w)
            else:  # scanned stacked groups: per-layer symmetric scales
                wq, s = jax.vmap(quantize_weights_int8)(w)
            out["w_q8"], out["w_scale"] = wq, s
        return out

    return walk(params, "")


def linear_int8(cache: Dict[str, Tuple[Array, Array]], path: str,
                x: Array, bias: Array = None,
                interpret: Optional[bool] = None) -> Array:
    """Run one cached linear through the integer kernel."""
    wq, s = cache[path]
    lead = x.shape[:-1]
    y = int8_matmul(x.reshape(-1, x.shape[-1]), wq, s, interpret=interpret)
    y = y.reshape(*lead, wq.shape[1])
    if bias is not None:
        y = y + bias
    return y
