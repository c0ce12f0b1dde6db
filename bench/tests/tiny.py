"""A tiny copy of the benchmark for CPU tests: an ``opt_tiny`` configuration
and a short mix in a temporary root beside the real ``bench/`` files."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

MODEL = {"activation_function": "relu", "do_layer_norm_before": True,
         "enable_bias": True, "ffn_dim": 512, "hidden_size": 128,
         "init_std": 0.02, "max_position_embeddings": 256,
         "num_attention_heads": 4, "num_hidden_layers": 4,
         "vocab_size": 2048, "word_embed_proj_dim": 128}


def config(precision: str = "bf16", limit: float = 0.05) -> dict:
    """The W8A8 configuration's file at ``opt_tiny`` size; ``bf16`` turns
    off the int8 program (bf16 pool, int8 control)."""
    real = json.loads((BENCH / "configs" /
                       "opt-125m-gated-w8a8.json").read_text())
    real.update(model=MODEL, source="test",
                engine={"batch_size": 4, "max_len": 256, "block_size": 16,
                        "token_budget": 32, "num_blocks": 64},
                limits={"max_gap": limit})
    real["program"] = dict(real["program"], constructor="opt_tiny")
    if precision == "bf16":
        real.update(precision="bf16", control={"bits": 8},
                    layout={"kv_bytes": 2, "kv_scale_bytes": 0,
                            "attn_q_bytes": 2})
    return real


CLOSED = {"loop": "closed", "clients": 8, "warm_widths": [1, 2, 4, 8],
          "prompt_tokens": {"median": 16, "sigma": 0.6, "min": 4, "max": 48},
          "output_tokens": {"median": 12, "sigma": 0.6, "min": 4, "max": 32},
          "max_total_tokens": 255, "strata": 8,
          "check": {"min_tokens": 40, "max_requests": 6}}

# a second mix the repository does not have: shorter requests, more clients
SHORT = {"loop": "closed", "clients": 12, "warm_widths": [1, 2, 4],
         "prompt_tokens": {"median": 8, "sigma": 0.7, "min": 2, "max": 24},
         "output_tokens": {"median": 6, "sigma": 0.7, "min": 2, "max": 16},
         "max_total_tokens": 255, "strata": 4,
         "check": {"min_tokens": 30, "max_requests": 8}}


def make_root(tmp: Path, precision: str = "bf16", limit: float = 0.05,
              mixes: dict = None) -> Path:
    """A root with ``bench/`` copied in, a tiny configuration and one cell
    per mix: ``tiny.<mix>``."""
    root = Path(tmp)
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (root / "bench" / "configs" / "tiny.json").write_text(
        json.dumps(config(precision, limit)))
    mixes = mixes or {"tiny-closed": CLOSED}
    for name, mix in mixes.items():
        (root / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(mix))
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "test"}]
    bench["workloads"] = [{"name": f"tiny.{m}", "config": "tiny",
                           "traffic": m, "chips": 1, "why": "test"}
                          for m in mixes]
    # every metric goes to every tiny cell
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if "workloads" in m:
                m["workloads"] = [w["name"] for w in bench["workloads"]]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root

# max_gap limits of the tiny configuration, from CPU readings on six seeds:
# the W8A8 program read 0 to 0.011 and its int4 control 0.11 to 0.34; the
# bf16 program read 0 (its int8 control 0 to 0.012, no separation here).
LIMITS = {"bf16": 0.05, "w8a8": 0.05}


# A decoder of another family than OPT that the program serves, at a tiny
# size: pre-RMSNorm, RoPE, GQA 4:1 at head_dim 48 (not 128 / 8), SwiGLU and
# an untied head. Its reference and program module are ``rope_gqa.py`` and
# ``rope_gqa_program.py`` beside this file.
ROPE_GQA_MODEL = {"hidden_size": 128, "num_attention_heads": 8,
                  "num_key_value_heads": 2, "head_dim": 48,
                  "intermediate_size": 256, "num_hidden_layers": 2,
                  "vocab_size": 2048, "max_position_embeddings": 256,
                  "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
                  "hidden_act": "silu", "tie_word_embeddings": False}

ROPE_GQA = {
    "source": "test", "model": ROPE_GQA_MODEL, "reduced": [],
    "program": {"module": "rope_gqa_program", "name": "rope-gqa-tiny"},
    "reference": "rope_gqa",
    "init": {"std": 0.02, "qk_std": 0.05, "norm_std": 0.02,
             "seed": 20240101},
    "departures": [], "assumed": {"weights": "random, from init.seed"},
    "precision": "bf16",
    "engine": {"batch_size": 4, "max_len": 256, "block_size": 16,
               "token_budget": 32, "num_blocks": 64},
    "layout": {"kv_bytes": 2, "kv_scale_bytes": 0, "attn_q_bytes": 2},
    "limits": {"max_gap": LIMITS["bf16"]}}
