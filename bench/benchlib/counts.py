"""Operations and bytes that a tick's work needs, computed from shapes.

Counts follow what the algorithm needs, not what a kernel happens to do:
padding positions, idle rows and the blocks past a row's own context are
not counted. So a roofline share means the same whatever kernel computes
it. ``model`` is a configuration's ``model`` section (published keys of the
OPT ``config.json``); ``layout`` its ``layout`` section (bytes per element
of the served KV pool, its scales and the attention query).
"""
from __future__ import annotations

from typing import Iterable, List, Tuple


def dims(model: dict) -> dict:
    d = model["hidden_size"]
    h = model["num_attention_heads"]
    return dict(d=d, h=h, hkv=model.get("num_key_value_heads", h),
                dh=d // h, ff=model["ffn_dim"], vocab=model["vocab_size"],
                layers=model["num_hidden_layers"])


def linear_shapes(model: dict) -> List[Tuple[int, int]]:
    """(K, N) of every matmul of one layer: q, k, v, o, fc1, fc2."""
    m = dims(model)
    d, hd, kvd = m["d"], m["h"] * m["dh"], m["hkv"] * m["dh"]
    return [(d, hd), (d, kvd), (d, kvd), (hd, d), (d, m["ff"]), (m["ff"], d)]


def int8_matmul_cost(m: int, k: int, n: int) -> Tuple[float, float]:
    """(ops, bytes) of an (m, k) x (k, n) int8 matmul with an f32 output."""
    return 2.0 * m * k * n, float(k * n + m * k + 4 * m * n)


def keys_seen(pos0: int, count: int) -> int:
    """Query-key pairs of ``count`` causal queries starting at ``pos0``."""
    return count * pos0 + count * (count + 1) // 2


def attn_row_cost(pos0: int, count: int, model: dict,
                  layout: dict) -> Tuple[float, float]:
    """(flops, bytes) of one layer of paged attention for one row: QK^T and
    PV over the row's own causal context, reading its K/V (and their
    per-token scales) once, and its queries, gate and output once."""
    m = dims(model)
    ctx = pos0 + count
    flops = 4.0 * m["h"] * m["dh"] * keys_seen(pos0, count)
    kv = ctx * m["hkv"] * m["dh"] * 2 * layout["kv_bytes"]
    scales = ctx * 2 * layout["kv_scale_bytes"]
    q_out = count * m["h"] * (2 * m["dh"] * layout["attn_q_bytes"] + 4)
    return flops, float(kv + scales + q_out)


def roofline_s(ops: float, nbytes: float, peak_ops: float,
               peak_bw: float) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_ops, t_mem = ops / peak_ops, nbytes / peak_bw
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


def tick_model_ops(rows: Iterable[Tuple[int, int]], sampled: int,
                   model: dict) -> float:
    """Model operations one tick's fed tokens need: every linear layer and
    the attention gate for each fed token, causal attention over each
    token's context, and the LM head only for rows that sample."""
    m = dims(model)
    rows = [(p, c) for p, c in rows if c > 0]
    fed = sum(c for _, c in rows)
    per_token = sum(k * n for k, n in linear_shapes(model)) + m["h"] * m["dh"]
    linear = 2.0 * fed * per_token * m["layers"]
    attn = sum(4.0 * m["h"] * m["dh"] * keys_seen(p, c) for p, c in rows)
    head = 2.0 * sampled * m["d"] * m["vocab"]
    return linear + attn * m["layers"] + head
