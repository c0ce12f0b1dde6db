"""Config registry: assigned architectures × input shapes.

Each arch module defines ``full()`` (the exact published config) and
``smoke()`` (a reduced same-family config for CPU tests), registered via
``register``. ``input_specs`` builds ShapeDtypeStruct stand-ins for every
(arch × shape) cell — shardable, weak-type-correct, zero allocation — which
the multi-pod dry-run lowers.

The paper's technique is selected per-run with ``method``:
    "vanilla" | "clipped_softmax" | "gated_attention"
applied uniformly to every softmax-attention block of any arch.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.gating import GateConfig
from repro.core.softmax import ClippedSoftmaxConfig
from repro.models.transformer import ModelConfig


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    step: str                    # "train" | "prefill" | "decode"


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}


@dataclasses.dataclass(frozen=True)
class ArchSpec:
    arch_id: str
    family: str                          # moe | dense | vlm | hybrid | ssm | audio
    full: Callable[..., ModelConfig]     # full() -> published config
    smoke: Callable[..., ModelConfig]    # smoke() -> reduced config
    # shapes this arch skips, with the reason (documented in DESIGN.md)
    skip_shapes: Tuple[Tuple[str, str], ...] = ()
    source: str = ""

    def skipped(self, shape: str) -> Optional[str]:
        for s, why in self.skip_shapes:
            if s == shape:
                return why
        return None


_REGISTRY: Dict[str, ArchSpec] = {}

SKIP_LONG = ("long_500k",
             "full softmax attention is quadratic; 500k decode reserved for "
             "sub-quadratic archs per assignment")
SKIP_DECODE_ENC = ("decode_32k", "encoder-only architecture has no autoregressive step")
SKIP_LONG_ENC = ("long_500k", "encoder-only architecture has no autoregressive step")


def register(spec: ArchSpec) -> ArchSpec:
    _REGISTRY[spec.arch_id] = spec
    return spec


def get_arch(arch_id: str) -> ArchSpec:
    _ensure_loaded()
    if arch_id not in _REGISTRY:
        raise KeyError(f"unknown arch {arch_id!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[arch_id]


def list_archs() -> List[str]:
    _ensure_loaded()
    return sorted(_REGISTRY)


def _ensure_loaded() -> None:
    # import arch modules for registration side-effects
    from repro.configs import (  # noqa: F401
        granite_moe_1b_a400m,
        qwen2_moe_a2_7b,
        phi_3_vision_4_2b,
        deepseek_67b,
        gemma2_27b,
        qwen3_14b,
        codeqwen1_5_7b,
        recurrentgemma_9b,
        xlstm_1_3b,
        hubert_xlarge,
        paper_models,
    )


def apply_method(cfg: ModelConfig, method: str,
                 gamma: float = -0.03, alpha: Optional[float] = None,
                 zeta: float = 1.0, pi_init: float = 0.5,
                 gate_kind: str = "linear") -> ModelConfig:
    """Inject the paper's technique into any ModelConfig."""
    if method == "vanilla":
        return dataclasses.replace(
            cfg, softmax_cfg=ClippedSoftmaxConfig(), gate_cfg=GateConfig(kind="none"))
    if method == "clipped_softmax":
        if alpha is not None and cfg.train_seq_len is None:
            raise ValueError(
                f"{cfg.name}: gamma = -alpha / T needs the length the model "
                f"is trained at (ModelConfig.train_seq_len)")
        sm = ClippedSoftmaxConfig(gamma=gamma, zeta=zeta, alpha=alpha)
        return dataclasses.replace(cfg, softmax_cfg=sm, gate_cfg=GateConfig(kind="none"))
    if method == "gated_attention":
        return dataclasses.replace(
            cfg, softmax_cfg=ClippedSoftmaxConfig(),
            gate_cfg=GateConfig.from_pi_init(pi_init, gate_kind))
    raise ValueError(f"unknown method {method!r}")


# --------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins, no allocation)
# --------------------------------------------------------------------------
def input_specs(cfg: ModelConfig, shape: ShapeSpec) -> Dict[str, jax.ShapeDtypeStruct]:
    """Model inputs for one cell. Decode cells additionally need the cache
    spec — see ``cache_specs``."""
    b, t = shape.global_batch, shape.seq_len
    sds = jax.ShapeDtypeStruct
    if shape.step == "train":
        if cfg.input_kind == "tokens":
            return {"tokens": sds((b, t), jnp.int32), "labels": sds((b, t), jnp.int32)}
        if cfg.input_kind == "embeds":
            return {
                "embeds": sds((b, t, cfg.frontend_dim or cfg.d_model), jnp.float32),
                "labels": sds((b, t), jnp.int32),
            }
        # mixed (vlm): image-patch prefix + text tokens
        n_img = cfg.n_prefix_embeds
        return {
            "embeds": sds((b, n_img, cfg.d_model), jnp.float32),
            "tokens": sds((b, t - n_img), jnp.int32),
            "labels": sds((b, t), jnp.int32),
        }
    if shape.step == "prefill":
        if cfg.input_kind == "tokens":
            return {"tokens": sds((b, t), jnp.int32)}
        if cfg.input_kind == "embeds":
            return {"embeds": sds((b, t, cfg.frontend_dim or cfg.d_model), jnp.float32)}
        n_img = cfg.n_prefix_embeds
        return {
            "embeds": sds((b, n_img, cfg.d_model), jnp.float32),
            "tokens": sds((b, t - n_img), jnp.int32),
        }
    # decode: one new token against a seq_len cache
    return {"tokens": sds((b, 1), jnp.int32)}


def cache_specs(cfg: ModelConfig, shape: ShapeSpec):
    """ShapeDtypeStruct pytree of the decode cache (via eval_shape)."""
    from repro.models.transformer import init_cache

    cfg_sized = dataclasses.replace(cfg, max_seq_len=max(shape.seq_len, cfg.window or 0))
    return jax.eval_shape(
        lambda: init_cache(cfg_sized, shape.global_batch, shape.seq_len,
                           dtype=cfg.compute_dtype)
    )


def to_bf16(cfg: ModelConfig) -> ModelConfig:
    return dataclasses.replace(cfg, param_dtype=jnp.bfloat16, compute_dtype=jnp.bfloat16)
