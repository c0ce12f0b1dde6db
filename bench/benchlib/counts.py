"""Operations and bytes that a tick's work needs, computed from shapes.

Counts follow what the algorithm needs, not what a kernel happens to do:
padding positions, idle rows, the blocks past a row's own context and the
keys outside a layer's window are not counted. So a roofline share means
the same whatever kernel computes it. The shapes are a ``Geometry``, which
each configuration's program module builds from its configuration file
(``geometry(config)``); ``layout`` is the file's ``layout`` section (bytes
per element of the served KV pool, its scales and the attention query).
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Iterable, List, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class Linear:
    """One matmul of a layer, (K, N), and the share of the fed tokens that
    take it: 1 for a dense matmul, ``top_k * experts_held / experts_total``
    for a routed expert."""
    k: int
    n: int
    per_token: float = 1.0


@dataclasses.dataclass(frozen=True)
class Layer:
    """One decoder layer: its matmuls, its attention heads, and ``window``,
    the keys a query sees (itself included) on a windowed layer, None where
    it sees its whole context. ``gate_ops`` is the multiply-adds per fed
    token of an attention gate (0 without one)."""
    linears: Tuple[Linear, ...]
    heads: int
    kv_heads: int
    head_dim: int
    window: Optional[int] = None
    gate_ops: int = 0

    def __post_init__(self):
        object.__setattr__(self, "linears", tuple(self.linears))


@dataclasses.dataclass(frozen=True)
class Geometry:
    """The layers of the model as this chip holds them, its width, and
    ``vocab``: the rows of the LM head this chip holds."""
    layers: Tuple[Layer, ...]
    d_model: int
    vocab: int

    def __post_init__(self):
        object.__setattr__(self, "layers", tuple(self.layers))

    def groups(self) -> List[Tuple[Layer, int]]:
        """Each distinct layer and how many times it occurs, in the order
        of its first occurrence."""
        return list(collections.Counter(self.layers).items())


def int8_matmul_cost(m: int, k: int, n: int) -> Tuple[float, float]:
    """(ops, bytes) of an (m, k) x (k, n) int8 matmul with an f32 output."""
    return 2.0 * m * k * n, float(k * n + m * k + 4 * m * n)


def keys_seen(pos0: int, count: int, window: Optional[int] = None) -> int:
    """Query-key pairs of ``count`` causal queries starting at ``pos0``,
    each seeing at most ``window`` keys, itself included."""
    if window is None or pos0 + count <= window:
        return count * pos0 + count * (count + 1) // 2
    whole = max(0, window - pos0)          # queries that see all before them
    return keys_seen(pos0, whole) + (count - whole) * window


def attn_row_cost(pos0: int, count: int, layer: Layer,
                  layout: dict) -> Tuple[float, float]:
    """(flops, bytes) of one layer of paged attention for one row: QK^T and
    PV over the keys each query sees, reading the K/V of those positions
    (and their per-token scales) once, and the row's queries, gate and
    output once. On a windowed layer the row reads the last ``window +
    count - 1`` positions at most: ``min(ctx, window)`` for a decode."""
    ctx = pos0 + count
    if layer.window is not None:
        ctx = min(ctx, layer.window + count - 1)
    flops = 4.0 * layer.heads * layer.head_dim * keys_seen(pos0, count,
                                                           layer.window)
    kv = ctx * layer.kv_heads * layer.head_dim * 2 * layout["kv_bytes"]
    scales = ctx * 2 * layout["kv_scale_bytes"]
    gate = 4 if layer.gate_ops else 0
    q_out = count * layer.heads * (2 * layer.head_dim * layout["attn_q_bytes"]
                                   + gate)
    return flops, float(kv + scales + q_out)


def roofline_s(ops: float, nbytes: float, peak_ops: float,
               peak_bw: float) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_ops, t_mem = ops / peak_ops, nbytes / peak_bw
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


def tick_model_ops(rows: Iterable[Tuple[int, int]], sampled: int,
                   geometry: Geometry) -> float:
    """Model operations one tick's fed tokens need: in every layer each
    linear for its share of the fed tokens and the attention gate for each,
    causal attention over the keys each token sees, and the LM head only
    for rows that sample."""
    rows = [(p, c) for p, c in rows if c > 0]
    fed = sum(c for _, c in rows)
    ops = 0.0
    for layer, n in geometry.groups():
        per_token = sum(lin.per_token * lin.k * lin.n
                        for lin in layer.linears) + layer.gate_ops
        attn = sum(4.0 * layer.heads * layer.head_dim
                   * keys_seen(p, c, layer.window) for p, c in rows)
        ops += 2.0 * fed * per_token * n + attn * n
    return ops + 2.0 * sampled * geometry.d_model * geometry.vocab
