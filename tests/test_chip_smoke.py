"""chip_smoke.py's phases on CPU: the script's control flow and its
reference comparison, at opt_tiny width and two layers.

The test injects the Pallas paged-attention kernel (``paged_backend=
"kernel"``), which runs in interpret mode off TPU; the W8A8 phases run the
int8 matmul kernel the same way. On CPU no tick holds a
``tpu_custom_call``, so the kernel-presence check is exercised on HLO text
instead. Prompts cross the 256-token budget, so a phase runs chunked
prefill, mixed ticks and pure decode as on the chip.
"""
import dataclasses
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import chip_smoke  # noqa: E402
from repro.configs import apply_method  # noqa: E402
from repro.configs.paper_models import opt_tiny  # noqa: E402

BASE = dataclasses.replace(opt_tiny(vocab=512, seq_len=512), n_layers=2,
                           paged_backend="kernel")
PHASE = dict(lengths=(24, 60, 130, 270), max_new=4, batch=4)


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "w8a8"])
@pytest.mark.parametrize("method", chip_smoke.METHODS)
def test_phase_matches_reference(method, quantized):
    r = chip_smoke.run_phase(BASE, method, quantized, **PHASE)
    assert r["served"] and r["tokens"] == 4 * PHASE["max_new"], r
    # every prompt's last position and every decode step is compared
    # (plus the ends of non-final prefill chunks)
    assert r["compared_rows"] >= 4 * PHASE["max_new"], r
    assert r["programs"] >= 2 and r["ticks"] > PHASE["max_new"], r
    assert r["err"] <= r["limit"] and r["ok"], r


def test_wrong_reference_fails():
    """The comparison can fail: a clipped engine checked against a
    reference that clips at a different gamma exceeds the limit."""
    orig = chip_smoke.reference_logits

    def far_gamma(cfg32, params32, ctx, seqs, positions):
        wrong = apply_method(cfg32, "clipped_softmax", gamma=-0.3)
        return orig(wrong, params32, ctx, seqs, positions)

    chip_smoke.reference_logits = far_gamma
    try:
        r = chip_smoke.run_phase(BASE, "clipped_softmax", False, **PHASE)
    finally:
        chip_smoke.reference_logits = orig
    assert r["served"] and r["err"] > r["limit"] and not r["ok"], r


def test_custom_call_kernels_reads_kernel_names():
    hlo = "\n".join([
        '  %paged_attention_mz.3 = (f32[8,12,1,1]) custom-call(%a), '
        'custom_call_target="tpu_custom_call", backend_config={}',
        '  %int8_matmul.7 = f32[8,768] custom-call(%b), '
        'custom_call_target="tpu_custom_call"',
        '  %other.1 = f32[2] custom-call(%c), custom_call_target="Sharding"',
    ])
    assert chip_smoke.custom_call_kernels(hlo) == {"paged_attention",
                                                  "int8_matmul"}
    assert chip_smoke.custom_call_kernels(hlo.split("\n")[2]) == set()


def test_refuses_ok_off_tpu(capsys):
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out
