"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run sets XLA_FLAGS before first
jax init and only then calls these.
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 = 256-chip single pod, or 2x16x16 = 512-chip two-pod mesh.

    Axis order puts 'pod' outermost (slowest links — DCI), then 'data'
    (intra-pod DP/FSDP), then 'model' (TP/EP, fastest ICI neighbours).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh() -> Mesh:
    """Whatever devices exist locally (tests / CPU smoke): (1, n)."""
    n = len(jax.devices())
    return _auto_mesh((1, n), ("data", "model"))
