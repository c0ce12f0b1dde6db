"""Serve OPT-125m on one TPU chip and check the served logits.

    python chip_smoke.py [--seed N]

Drives the serving path a user calls — ``ContinuousBatcher(paged=True)``
over the paged KV pool, with the Pallas paged-attention kernel — at
OPT-125m's published widths (12 layers, d=768, 12 heads, d_ff=3072, vocab
50272) with random weights from ``--seed``. Six phases: the paper's three
attention variants (vanilla, clipped softmax, gated attention), each in
bf16 and as the W8A8 + int8-KV program (``qconfig=QConfig()``).

Each phase serves 8 requests (prompts of 64-512 tokens, 32 new tokens
each) at batch 8, which runs chunked prefill, mixed prefill/decode ticks
and pure decode ticks. Every logit row the engine's tick produces (each
prefill chunk's last position, hence each prompt's last position, and
every decode step) is compared with a plain reference on the same tokens:
a no-cache float32 ``model_apply`` at ``precision="highest"`` for bf16,
and the same model under the engine's calibrated fake-quant
``QuantContext`` for W8A8. The error is max |engine - reference| over
those rows divided by max |reference| (``LIMITS`` below).

One line per phase: variant, dtype, compile seconds of the tick programs,
ticks, generated tokens, the error and its limit, and whether the compiled
tick holds the paged-attention kernel (and, under W8A8, the int8 matmul)
as a ``tpu_custom_call``. The last line is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase passed
on a TPU. Anything else exits non-zero without it.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs import apply_method, to_bf16  # noqa: E402
from repro.configs.paper_models import opt_125m  # noqa: E402
from repro.models import model_apply, model_init  # noqa: E402
from repro.quant import NO_QUANT, QConfig, QuantContext  # noqa: E402
from repro.serving import ContinuousBatcher, Request  # noqa: E402
from repro.serving.decode import mixed_tick  # noqa: E402

METHODS = ("vanilla", "clipped_softmax", "gated_attention")
# max |engine - reference| / max |reference| over the compared logit rows.
# bf16: activations and the KV pool in bf16 against an f32 reference on
# the same (bf16-valued) weights — a few bf16 ulps (2^-8) through 12
# layers. W8A8: int8 matmul inputs + int8 KV against fake-quant of every
# activation site (the reference quantizes more sites, and not the KV
# cache) — a few int8 steps of the logit range. At 6 layers x d=384 on CPU
# these read 0.008 and 0.045.
LIMITS = {"bf16": 0.03, "w8a8": 0.15}
KERNELS = ("paged_attention", "int8_matmul")


class TickTap:
    """Stands in for the engine's jitted tick: the same program
    (``serving.decode.mixed_tick``, which ``make_mixed_step`` jits without
    its last output), also returning each row's last-position logits.
    Compiles each tick shape ahead of time to time it and read its HLO,
    and records every row's logits with the request and position they
    belong to."""

    def __init__(self, engine: ContinuousBatcher):
        self.engine = engine
        self.jitted = jax.jit(mixed_tick(engine.cfg, engine._gen,
                                         engine._qctx), static_argnums=(6,))
        self.programs = {}
        self.compile_s = 0.0
        self.kernels = set()
        self.rows = []           # (uid, position, logits row)
        engine._step_fn = self

    def __call__(self, params, cache, tokens, pos, counts, keys, live_width,
                 live_widths):
        shape = (tokens.shape, live_width)
        if shape not in self.programs:
            t0 = time.perf_counter()
            prog = self.jitted.lower(params, cache, tokens, pos, counts, keys,
                                     live_width, live_widths).compile()
            self.compile_s += time.perf_counter() - t0
            self.programs[shape] = prog
            self.kernels |= custom_call_kernels(prog.as_text())
        nxt, cache, last = self.programs[shape](
            params, cache, tokens, pos, counts, keys, live_widths)
        pos, counts, last = (np.asarray(a) for a in (pos, counts, last))
        for i in np.flatnonzero(counts):
            uid = self.engine.slots[i].req.uid
            self.rows.append((uid, int(pos[i] + counts[i] - 1), last[i]))
        return nxt, cache


def custom_call_kernels(hlo_text: str) -> set:
    """Names in ``KERNELS`` that some ``tpu_custom_call`` of the program
    carries (a Pallas kernel's instruction is named after its call)."""
    names = re.findall(r"%([\w.]+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
                       hlo_text)
    return {k for k in KERNELS if any(n.startswith(k) for n in names)}


def reference_logits(cfg32, params32, ctx, seqs, positions):
    """Cache-free float32 logits at ``positions`` ((B, P) int) of each
    padded sequence ((B, T) int32); causal, so the padding never reaches a
    compared position."""
    def fwd(p, toks, at):
        logits = model_apply(p, cfg32, {"tokens": toks}, ctx=ctx)[0]
        return jnp.take_along_axis(logits, at[:, :, None], axis=1)

    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(fwd)(params32, jnp.asarray(seqs),
                                       jnp.asarray(positions)))


def run_phase(base_cfg, method: str, quantized: bool, *, seed: int = 0,
              lengths=tuple(range(64, 513, 64)), max_new: int = 32,
              batch: int = 8, block_size: int = 16) -> dict:
    """Serve ``lengths`` prompts through one engine and compare its logits
    with the reference. Returns the phase's record (``ok`` included)."""
    kw = dict(alpha=4.0) if method == "clipped_softmax" else {}
    cfg32 = apply_method(base_cfg, method, **kw)
    cfg = to_bf16(cfg32)
    params = model_init(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(4, cfg.vocab_size, size=n).astype(np.int32)
               for n in lengths]
    max_len = -(-(max(lengths) + max_new) // block_size) * block_size

    t0 = time.perf_counter()
    engine = ContinuousBatcher(params, cfg, batch_size=batch, max_len=max_len,
                               paged=True, block_size=block_size,
                               qconfig=QConfig() if quantized else None)
    setup_s = time.perf_counter() - t0
    tap = TickTap(engine)
    for uid, p in enumerate(prompts):
        engine.submit(Request(uid=uid, prompt=p, max_new_tokens=max_new))
    ticks = 0
    t0 = time.perf_counter()
    while engine.queue or any(s.req for s in engine.slots):
        engine.step()
        ticks += 1
    serve_s = time.perf_counter() - t0
    outputs = {r.uid: r.output for r in engine.done}
    served = len(outputs) == len(prompts) and not engine.failed and all(
        len(outputs[u]) == max_new for u in range(len(prompts)))

    err = float("inf")
    if served:
        ref_ctx = NO_QUANT
        if quantized:
            ref_ctx = QuantContext(engine.qconfig)
            ref_ctx.load_ranges(engine._qctx.ranges)       # fake-quant mode
        seqs = np.zeros((len(prompts), max_len), np.int32)
        for u, p in enumerate(prompts):
            s = np.concatenate([p, outputs[u][:-1]])
            seqs[u, :len(s)] = s
        by_uid = {u: [(q, row) for v, q, row in tap.rows if v == u]
                  for u in range(len(prompts))}
        width = max(len(r) for r in by_uid.values())
        at = np.zeros((len(prompts), width), np.int32)
        for u, rows in by_uid.items():
            at[u, :len(rows)] = [q for q, _ in rows]
        params32 = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), params)
        ref = reference_logits(cfg32, params32, ref_ctx, seqs, at)
        diff = max(float(np.max(np.abs(row - ref[u, j])))
                   for u, rows in by_uid.items()
                   for j, (_, row) in enumerate(rows))
        scale = max(float(np.max(np.abs(ref[u, :len(rows)])))
                    for u, rows in by_uid.items())
        err = diff / scale

    dtype = "w8a8" if quantized else "bf16"
    want = set(KERNELS) if quantized else {"paged_attention"}
    return dict(variant=method, dtype=dtype, compile_s=tap.compile_s,
                setup_s=setup_s, serve_s=serve_s, programs=len(tap.programs),
                ticks=ticks, tokens=sum(len(o) for o in outputs.values()),
                compared_rows=len(tap.rows), err=err, limit=LIMITS[dtype],
                kernels=sorted(tap.kernels), served=served,
                ok=served and err <= LIMITS[dtype],
                kernels_ok=want <= tap.kernels)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    enable_compile_cache()
    ok = True
    for method in METHODS:
        for quantized in (False, True):
            r = run_phase(opt_125m(), method, quantized, seed=args.seed)
            ok &= r["ok"] and r["kernels_ok"]
            print(json.dumps(r), flush=True)
    if not ok:
        print("chip_smoke: a phase failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
