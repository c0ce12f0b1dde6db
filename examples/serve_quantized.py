"""Serve a model with batched requests through the W8A8-simulated path:
prefill + decode with a calibrated QuantContext, plus the int8 MXU kernel
on the LM head as the hardware-exact reference.

    PYTHONPATH=src python examples/serve_quantized.py
"""
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs import apply_method
from repro.configs.paper_models import opt_tiny
from repro.data import SyntheticLM, SyntheticLMConfig
from repro.kernels import linear_w8a8, quantize_weights_int8
from repro.models import model_apply, model_init
from repro.quant import QConfig, calibrate
from repro.serving import GenerateConfig, generate


def main() -> None:
    enable_compile_cache()
    cfg = apply_method(opt_tiny(vocab=512, seq_len=64), "clipped_softmax",
                       alpha=4.0)
    cfg = dataclasses.replace(cfg, max_seq_len=128)
    params = model_init(jax.random.PRNGKey(0), cfg)
    data = SyntheticLM(SyntheticLMConfig(vocab_size=512, seq_len=64,
                                         batch_size=4))

    # calibrate W8A8
    def apply_fn(p, b, ctx):
        return model_apply(p, cfg, b, ctx=ctx)[0]

    cal = [jax.tree_util.tree_map(jnp.asarray, data.batch(i)) for i in range(4)]
    ctx = calibrate(apply_fn, params, cal, QConfig(), 4)
    print(f"calibrated {len(ctx.ranges)} activation sites")

    # batched generation (FP path) — one fused jitted decode loop
    prompts = jnp.asarray(data.batch(99)["tokens"][:, :16])
    t0 = time.perf_counter()
    out = generate(params, cfg, prompts, GenerateConfig(max_new_tokens=16))
    dt = time.perf_counter() - t0
    n_new = out.shape[0] * 16
    print(f"generated {out.shape} in {dt:.2f}s "
          f"({n_new / dt:.1f} tok/s batched, greedy)")

    # sampling path: temperature + top-k, early EOS with padding
    out_s = generate(params, cfg, prompts,
                     GenerateConfig(max_new_tokens=16, temperature=0.8,
                                    top_k=20, eos_id=5),
                     key=jax.random.PRNGKey(42))
    stopped = int((out_s[:, 16:] == 5).any(axis=1).sum())
    print(f"sampled top-k=20 T=0.8: {out_s.shape}, "
          f"{stopped}/{out_s.shape[0]} rows hit EOS early")

    # hardware-exact int8 matmul on the LM head (the op the paper's method
    # makes safe): compare against the float head
    x = jax.random.normal(jax.random.PRNGKey(1), (64, cfg.d_model))
    w = params["embed"]["table"].T  # tied head (d_model, vocab)
    wq, ws = quantize_weights_int8(w)
    y_int8 = linear_w8a8(x, wq, ws)
    y_fp = x @ w
    rel = float(jnp.mean(jnp.abs(y_int8 - y_fp)) / jnp.mean(jnp.abs(y_fp)))
    print(f"int8 MXU-path LM head vs fp: rel err {rel:.4f}")


if __name__ == "__main__":
    main()
