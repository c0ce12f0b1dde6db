"""Program module of the test configuration in ``rope_gqa.py``: the
program's ``ModelConfig`` built from the file's published keys, and the
counts' geometry of its layers (GQA at a head size other than d/h, SwiGLU,
an untied head)."""
from __future__ import annotations

from benchlib import counts


def program_config(config: dict):
    from repro.configs import to_bf16
    from repro.models.transformer import ModelConfig

    m = config["model"]
    if m["hidden_act"] != "silu" or m["rms_norm_eps"] != 1e-6:
        raise ValueError("the program serves SiLU-gated MLPs and RMSNorm at "
                         "eps 1e-6 only")
    return to_bf16(ModelConfig(
        name=config["program"]["name"], n_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], d_head=m["head_dim"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        mlp_kind="swiglu", norm="rmsnorm", pos="rope",
        rope_theta=m["rope_theta"],
        max_seq_len=m["max_position_embeddings"],
        tie_embeddings=m["tie_word_embeddings"], scan_layers=False,
        remat=False))


def geometry(config: dict) -> counts.Geometry:
    m = config["model"]
    d, h, hkv = (m["hidden_size"], m["num_attention_heads"],
                 m["num_key_value_heads"])
    dh, ff = m["head_dim"], m["intermediate_size"]
    layer = counts.Layer(
        linears=[counts.Linear(d, h * dh), counts.Linear(d, hkv * dh),
                 counts.Linear(d, hkv * dh), counts.Linear(h * dh, d),
                 counts.Linear(d, ff), counts.Linear(d, ff),
                 counts.Linear(ff, d)],
        heads=h, kv_heads=hkv, head_dim=dh)
    return counts.Geometry([layer] * m["num_hidden_layers"], d,
                           m["vocab_size"])
