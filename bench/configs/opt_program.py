"""The program module of the ``opt-125m-gated-*`` configurations: how the
program under test is built for them, and what their work counts.

``program_config`` builds the program's ``ModelConfig`` through the
program's own constructor and checks it against the published widths that
the configuration file states. ``geometry`` describes the model to the
benchmark's counts (``benchlib.counts``) from the file alone: twelve
identical pre-LayerNorm layers with q, k, v, o, fc1 and fc2, full causal
attention, and the paper's linear gate per head where the file's method is
``gated_attention``. Unlike the reference beside it, this module may import
the program.
"""
from __future__ import annotations

from benchlib import counts


def program_config(config: dict):
    """The program's ModelConfig for this configuration, checked against
    the published widths the configuration file states."""
    from repro.configs import apply_method, paper_models, to_bf16

    prog = config["program"]
    gate = prog.get("gate", {})
    cfg = apply_method(getattr(paper_models, prog["constructor"])(),
                       prog["method"], pi_init=gate.get("pi_init", 0.5),
                       gate_kind=gate.get("kind", "linear"))
    cfg = to_bf16(cfg)
    m = config["model"]
    want = {"n_layers": m["num_hidden_layers"], "d_model": m["hidden_size"],
            "n_heads": m["num_attention_heads"],
            "n_kv_heads": m["num_attention_heads"], "d_ff": m["ffn_dim"],
            "vocab_size": m["vocab_size"], "padded_vocab": m["vocab_size"],
            "max_seq_len": m["max_position_embeddings"],
            "head_dim": m["hidden_size"] // m["num_attention_heads"],
            "mlp_kind": m["activation_function"],
            "norm_position": "pre" if m["do_layer_norm_before"] else "post",
            "tie_embeddings": True, "pos": "learned", "causal": True}
    got = {k: getattr(cfg, k) for k in want}
    if got != want or m["word_embed_proj_dim"] != m["hidden_size"]:
        raise ValueError(f"program config {got} is not the published {want}")
    return cfg


def geometry(config: dict) -> counts.Geometry:
    """OPT's layers as the counts see them, from the file's ``model``
    section and, for the gate, its program's method."""
    m = config["model"]
    d, h = m["hidden_size"], m["num_attention_heads"]
    dh, ff = d // h, m["ffn_dim"]
    gated = config["program"]["method"] == "gated_attention"
    layer = counts.Layer(
        linears=[counts.Linear(k, n) for k, n in
                 [(d, d)] * 4 + [(d, ff), (ff, d)]],
        heads=h, kv_heads=h, head_dim=dh, gate_ops=h * dh if gated else 0)
    return counts.Geometry([layer] * m["num_hidden_layers"], d,
                           m["vocab_size"])
