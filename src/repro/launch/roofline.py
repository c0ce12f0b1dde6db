"""Roofline-term extraction from AOT-compiled modules.

Hardware model (TPU v5e-class, per chip):
    peak bf16 compute : 197 TFLOP/s
    HBM bandwidth     : 819 GB/s
    ICI link bandwidth: ~50 GB/s/link

The compiled HLO is the *partitioned* (per-device) module, so
``cost_analysis()`` FLOPs/bytes and the collective shapes parsed from
``as_text()`` are per-device quantities. The three roofline terms are
therefore per-device seconds (equivalent to aggregate / (chips x rate)):

    compute    = flops_per_device / PEAK_FLOPS
    memory     = hbm_bytes_per_device / HBM_BW
    collective = wire_bytes_per_device / ICI_BW

Wire bytes use ring-algorithm multipliers derived from the parsed
``replica_groups`` size S:
    all-reduce        2 (S-1)/S x buffer
    all-gather          (S-1)/S x gathered result
    reduce-scatter      (S-1)/S x input        (= result x S x (S-1)/S)
    all-to-all          (S-1)/S x buffer
    collective-permute  1        x buffer
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, List, Optional, Tuple

PEAK_FLOPS = 197e12        # bf16 FLOP/s per chip
HBM_BW = 819e9             # bytes/s per chip
ICI_BW = 50e9              # bytes/s per link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*(?:\()?([a-z0-9,\[\]\{\}\s]+?)(?:\))?\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(", re.IGNORECASE)
_SHAPE_RE = re.compile(r"(pred|s8|u8|s16|u16|s32|u32|s64|u64|f16|bf16|f32|f64|f8e4m3fn|f8e5m2)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_RE2 = re.compile(r"replica_groups=\{([^}]*)\}")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_RE2.search(line)
    if m:
        first = m.group(1).split("}")[0].split(",")
        return max(len(first), 1)
    return default


@dataclasses.dataclass
class CollectiveStats:
    counts: Dict[str, int]
    buffer_bytes: Dict[str, float]   # per-device buffer bytes by op kind
    wire_bytes: float                # ring-model bytes on the wire / device

    def as_dict(self):
        return {"counts": self.counts, "buffer_bytes": self.buffer_bytes,
                "wire_bytes": self.wire_bytes}


def parse_collectives(hlo_text: str, default_group: int = 16) -> CollectiveStats:
    counts: Dict[str, int] = {}
    buf: Dict[str, float] = {}
    wire = 0.0
    for line in hlo_text.splitlines():
        if "-done" in line:
            continue   # async pairs counted at -start
        m = _COLL_RE.search(line)
        if not m:
            continue
        result_types, kind = m.group(1), m.group(2).lower()
        nbytes = _shape_bytes(result_types)
        if nbytes == 0:
            continue
        s = _group_size(line, default_group)
        frac = (s - 1) / max(s, 1)
        if kind == "all-reduce":
            w = 2.0 * frac * nbytes
        elif kind == "all-gather":
            w = frac * nbytes                     # result is gathered size
        elif kind == "reduce-scatter":
            w = frac * nbytes * s                 # input = result x S
        elif kind == "all-to-all":
            w = frac * nbytes
        else:                                      # collective-permute
            w = float(nbytes)
        counts[kind] = counts.get(kind, 0) + 1
        buf[kind] = buf.get(kind, 0.0) + nbytes
        wire += w
    return CollectiveStats(counts, buf, wire)


@dataclasses.dataclass
class Roofline:
    flops_per_device: float
    hbm_bytes_per_device: float
    wire_bytes_per_device: float
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops: Optional[float] = None       # 6*N*D (per device share)
    useful_flops_ratio: Optional[float] = None
    collectives: Optional[dict] = None
    memory_stats: Optional[dict] = None

    def as_dict(self):
        return dataclasses.asdict(self)


def analyze(compiled, n_chips: int,
            model_flops_total: Optional[float] = None) -> Roofline:
    """Build the three-term roofline from one compiled executable."""
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))
    hbm = float(ca.get("bytes accessed", 0.0))
    txt = compiled.as_text()
    colls = parse_collectives(txt)
    compute_s = flops / PEAK_FLOPS
    memory_s = hbm / HBM_BW
    collective_s = colls.wire_bytes / ICI_BW
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    bottleneck = max(terms, key=terms.get)
    ms = compiled.memory_analysis()
    mem_stats = {
        "argument_bytes": int(ms.argument_size_in_bytes),
        "output_bytes": int(ms.output_size_in_bytes),
        "temp_bytes": int(ms.temp_size_in_bytes),
        "code_bytes": int(ms.generated_code_size_in_bytes),
    }
    r = Roofline(
        flops_per_device=flops,
        hbm_bytes_per_device=hbm,
        wire_bytes_per_device=colls.wire_bytes,
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        bottleneck=bottleneck,
        collectives=colls.as_dict(),
        memory_stats=mem_stats,
    )
    if model_flops_total:
        per_dev = model_flops_total / n_chips
        r.model_flops = per_dev
        r.useful_flops_ratio = per_dev / flops if flops else None
    return r


def model_flops_train(n_params_active: int, n_tokens: int) -> float:
    """6*N*D rule (fwd 2ND + bwd 4ND)."""
    return 6.0 * n_params_active * n_tokens


def model_flops_infer(n_params_active: int, n_tokens: int) -> float:
    return 2.0 * n_params_active * n_tokens
