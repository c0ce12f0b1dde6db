"""Quickstart: build a quantizable transformer with the paper's two
modifications, run a forward pass, inspect outlier metrics, quantize.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs import apply_method, get_arch, list_archs
from repro.configs.paper_models import opt_tiny
from repro.core import OutlierStats, clipped_softmax, infinity_norm, kurtosis
from repro.models import model_apply, model_init
from repro.quant import QConfig, QuantContext, calibrate


def main() -> None:
    enable_compile_cache()
    print("Assigned architecture pool:", ", ".join(list_archs()))

    # 1. the paper's core op: exact zeros with finite logits
    logits = jnp.array([[0.0, 1.0, 6.0, 6.0]])
    print("\nclipped_softmax(gamma=-0.03):", clipped_softmax(logits, -0.03))

    # 2. any pool arch + any method, one switch
    cfg = apply_method(get_arch("qwen3-14b").smoke(), "gated_attention",
                       pi_init=0.5)
    params = model_init(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jnp.ones((2, 16), jnp.int32) * 5}
    out, aux = model_apply(params, cfg, batch, collect_acts=True)
    print(f"\n{cfg.name}: logits {out.shape}")

    # 3. the paper's outlier telemetry
    stats = OutlierStats()
    stats.update(aux["attn_outputs"])
    print("outlier metrics:", stats.summary())

    # 4. PTQ in three lines
    cfg2 = apply_method(opt_tiny(vocab=256, seq_len=32), "clipped_softmax",
                        alpha=4.0)
    p2 = model_init(jax.random.PRNGKey(1), cfg2)

    def apply_fn(p, b, ctx):
        return model_apply(p, cfg2, b, ctx=ctx)[0]

    batches = [{"tokens": jax.random.randint(jax.random.PRNGKey(i),
                                             (4, 32), 0, 256)}
               for i in range(4)]
    ctx = calibrate(apply_fn, p2, batches, QConfig(), 4)
    q_logits = apply_fn(p2, batches[0], ctx)
    print(f"\nW8A8 simulated forward: {q_logits.shape}, "
          f"{len(ctx.ranges)} calibrated sites")


if __name__ == "__main__":
    main()
