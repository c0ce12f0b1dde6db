"""CPU tests of the benchmark's yardstick: trace reduction, operation and
byte counts, peaks and traffic. No JAX device is touched."""
import math
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchlib import counts, peaks, trace, traffic  # noqa: E402

OPT125 = {"hidden_size": 768, "num_attention_heads": 12, "ffn_dim": 3072,
          "vocab_size": 50272, "num_hidden_layers": 12}
W8A8 = {"kv_bytes": 1, "kv_scale_bytes": 4, "attn_q_bytes": 4}


# ------------------------------------------------------------------ trace
def _trace():
    ops = [("paged_attention", 0.0, 1.0), ("fusion", 0.5, 1.5),
           ("int8_matmul", 2.0, 2.5), ("paged_attention", 3.0, 3.2),
           ("copy", 9.0, 9.5)]
    spans = [("bench.window", 0.0, 4.0), ("bench.step", 0.0, 1.6),
             ("bench.client", 1.6, 1.9), ("bench.step", 1.9, 3.3),
             ("bench.submit", 3.3, 4.0)]
    return trace.Trace({"/device:TPU:0": ops}, spans)


def test_union_merges_overlaps_and_drops_empty():
    assert trace.union([(2, 3), (0, 1), (0.5, 1.5), (4, 4)]) == \
        [(0, 1.5), (2, 3)]


def test_reduce_busy_idle_and_kernel_time_by_prefix():
    red = trace.reduce(_trace(), 0.0, 4.0)
    assert red.window_s == 4.0
    assert red.busy == [(0.0, 1.5), (2.0, 2.5), (3.0, 3.2)]
    assert math.isclose(red.busy_s, 2.2)
    assert math.isclose(red.kernel_time("paged_attention"), 1.2)
    assert red.kernel_time("int8_matmul") == 0.5
    assert "copy" not in red.kernel_s          # outside the window


def test_idle_gaps_named_by_covering_span():
    red = trace.reduce(_trace(), 0.0, 4.0)
    bd = trace.breakdown(red)
    assert bd["device_ops"][0][0] == "paged_attention"
    gaps = [(n, round(s, 6)) for n, s in bd["idle_gaps"]]
    assert gaps == [("bench.submit", 0.8), ("bench.client", 0.5),
                    ("bench.step", 0.5)]


def test_step_self_time_is_span_minus_device_time():
    red = trace.reduce(_trace(), 0.0, 4.0)
    host, n = trace.self_time(red, "bench.step")
    # span 1: 1.6 s, 1.5 covered; span 2: 1.4 s, 0.7 covered
    assert n == 2 and math.isclose(host, 0.1 + 0.7)


def test_op_name_from_hlo_text():
    assert trace.op_name("%paged_attention.3 = f32[8]{0} custom-call()") == \
        "paged_attention"
    assert trace.op_name("%copy-start.80 = (s32[64]) copy-start(x)") == \
        "copy-start"
    assert trace.op_name("%fusion = f32[] fusion()") == "fusion"


def test_two_chips_average_busy_time():
    t = _trace()
    t.device_ops["/device:TPU:1"] = [("fusion", 0.0, 4.0)]
    red = trace.reduce(t, 0.0, 4.0)
    assert math.isclose(red.busy_s, (2.2 + 4.0) / 2)


# ------------------------------------------------------------------ counts
def test_linear_shapes_and_int8_cost_at_opt125m():
    shapes = counts.linear_shapes(OPT125)
    assert shapes == [(768, 768)] * 4 + [(768, 3072), (3072, 768)]
    assert sum(k * n for k, n in shapes) == 7_077_888
    ops, nbytes = counts.int8_matmul_cost(64, 768, 3072)
    assert ops == 2 * 64 * 768 * 3072
    assert nbytes == 768 * 3072 + 64 * 768 + 4 * 64 * 3072


def test_attention_row_cost_by_hand():
    # a decode token at position 1000 reads 1001 keys of 12 heads x 64
    flops, nbytes = counts.attn_row_cost(1000, 1, OPT125, W8A8)
    assert flops == 4 * 12 * 64 * 1001
    assert nbytes == 1001 * 12 * 64 * 2 * 1 + 1001 * 2 * 4 + 12 * (2 * 64 * 4 + 4)
    # a 3-token chunk at 0 sees 1 + 2 + 3 keys
    assert counts.keys_seen(0, 3) == 6 and counts.keys_seen(10, 2) == 23


def test_roofline_picks_the_binding_bound():
    assert counts.roofline_s(197e12, 1.0, 197e12, 819e9) == (1.0, "compute")
    t, which = counts.roofline_s(1.0, 819e9, 197e12, 819e9)
    assert (t, which) == (1.0, "memory")


def test_tick_model_ops_counts_head_only_for_sampling_rows():
    rows = [(100, 1), (0, 4), (7, 0)]
    got = counts.tick_model_ops(rows, sampled=1, model=OPT125)
    per_token = 7_077_888 + 12 * 64
    want = 2 * 5 * per_token * 12 + 4 * 768 * (101 + 10) * 12 \
        + 2 * 768 * 50272
    assert got == want


# ------------------------------------------------------------------ peaks
def test_peaks_for_v5e_and_unknown_device_is_an_error():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert peaks.matmul_peak(p, "w8a8") == 393e12
    assert peaks.matmul_peak(p, "bf16") == 197e12
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v9 imaginary")


# ------------------------------------------------------------------ traffic
MIX = {"loop": "closed", "clients": 8,
       "prompt_tokens": {"median": 128, "sigma": 0.6, "min": 16, "max": 512},
       "output_tokens": {"median": 512, "sigma": 0.6, "min": 64, "max": 1536},
       "max_total_tokens": 2047, "strata": 64}


def _take(mix, seed, n, vocab=50272):
    it = traffic.stream(mix, seed, vocab)
    return [next(it) for _ in range(n)]


@pytest.mark.parametrize("seed", [0, 2**31 + 11, 2**40 + 3])
def test_stream_is_deterministic_clipped_and_capped(seed):
    a, b = _take(MIX, seed, 200), _take(MIX, seed, 200)
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert x.max_new_tokens == y.max_new_tokens
    for d in a:
        assert 16 <= len(d.prompt) <= 512
        assert 1 <= d.max_new_tokens <= 1536
        assert len(d.prompt) + d.max_new_tokens <= 2047
        assert d.prompt.min() >= 0 and d.prompt.max() < 50272


def test_seeds_draw_the_same_sizes_in_another_order():
    a, b = _take(MIX, 5, 128), _take(MIX, 2**33 + 5, 128)
    assert sorted(len(d.prompt) for d in a) == sorted(len(d.prompt) for d in b)
    assert sorted(d.max_new_tokens for d in a) == \
        sorted(d.max_new_tokens for d in b)
    assert [len(d.prompt) for d in a] != [len(d.prompt) for d in b]


def test_midway_shares_are_stratified_uniform_and_fixed():
    s = traffic.midway_shares(64)
    assert sorted(s) == sorted((np.arange(64) + 0.5) / 64)
    assert np.array_equal(s, traffic.midway_shares(64))


def test_midway_sends_the_generated_part_with_the_prompt():
    d = _take(MIX, 2**31 + 7, 1)[0]
    m = traffic.midway(d, 0.25)
    done = d.max_new_tokens - m.max_new_tokens
    assert m.max_new_tokens == max(1, round(0.25 * d.max_new_tokens))
    assert np.array_equal(m.prompt, np.concatenate([d.prompt,
                                                    d.filler[:done]]))
    assert len(m.prompt) + m.max_new_tokens == \
        len(d.prompt) + d.max_new_tokens
    assert traffic.midway(d, 1.0).max_new_tokens == d.max_new_tokens
