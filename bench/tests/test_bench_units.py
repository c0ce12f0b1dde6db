"""CPU tests of the benchmark's yardstick: trace reduction, operation and
byte counts and the readers that use them, peaks, traffic, and the padding
of the reference comparison. Only the last runs JAX, on the CPU."""
import json
import math
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "configs"))
sys.path.insert(0, str(BENCH / "tests"))

from benchlib import check, counts, driver, peaks, readers, trace, traffic  # noqa: E402
import opt_program  # noqa: E402

A_CONFIG = json.loads((BENCH / "configs" /
                       "opt-125m-gated-w8a8.json").read_text())
OPT125 = opt_program.geometry(A_CONFIG)
W8A8 = {"kv_bytes": 1, "kv_scale_bytes": 4, "attn_q_bytes": 4}
V5E = peaks.peaks_for("TPU v5 lite")


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
    assert len(OPT125.layers) == 12 and len(OPT125.groups()) == 1
    layer = OPT125.layers[0]
    shapes = [(lin.k, lin.n) for lin in layer.linears]
    assert shapes == [(768, 768)] * 4 + [(768, 3072), (3072, 768)]
    assert all(lin.per_token == 1 for lin in layer.linears)
    assert sum(k * n for k, n in shapes) == 7_077_888
    assert (layer.heads, layer.kv_heads, layer.head_dim) == (12, 12, 64)
    assert layer.window is None and layer.gate_ops == 12 * 64
    assert (OPT125.d_model, OPT125.vocab) == (768, 50272)
    ops, nbytes = counts.int8_matmul_cost(64, 768, 3072)
    assert ops == 2 * 64 * 768 * 3072
    assert nbytes == 768 * 3072 + 64 * 768 + 4 * 64 * 3072


def test_attention_row_cost_by_hand():
    # a decode token at position 1000 reads 1001 keys of 12 heads x 64
    flops, nbytes = counts.attn_row_cost(1000, 1, OPT125.layers[0], W8A8)
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
    got = counts.tick_model_ops(rows, sampled=1, geometry=OPT125)
    per_token = 7_077_888 + 12 * 64
    want = 2 * 5 * per_token * 12 + 4 * 768 * (101 + 10) * 12 \
        + 2 * 768 * 50272
    assert got == want


# The counts and readers as they were before the geometry, for OPT only:
# the readings of cell A may not move.
def _frozen_dims(model):
    d, h = model["hidden_size"], model["num_attention_heads"]
    return dict(d=d, h=h, hkv=model.get("num_key_value_heads", h), dh=d // h,
                ff=model["ffn_dim"], vocab=model["vocab_size"],
                layers=model["num_hidden_layers"])


def _frozen_linear_shapes(model):
    m = _frozen_dims(model)
    d, hd, kvd = m["d"], m["h"] * m["dh"], m["hkv"] * m["dh"]
    return [(d, hd), (d, kvd), (d, kvd), (hd, d), (d, m["ff"]), (m["ff"], d)]


def _frozen_attn_row_cost(pos0, count, model, layout):
    m = _frozen_dims(model)
    ctx = pos0 + count
    flops = 4.0 * m["h"] * m["dh"] * counts.keys_seen(pos0, count)
    kv = ctx * m["hkv"] * m["dh"] * 2 * layout["kv_bytes"]
    scales = ctx * 2 * layout["kv_scale_bytes"]
    q_out = count * m["h"] * (2 * m["dh"] * layout["attn_q_bytes"] + 4)
    return flops, float(kv + scales + q_out)


def _frozen_tick_model_ops(rows, sampled, model):
    m = _frozen_dims(model)
    rows = [(p, c) for p, c in rows if c > 0]
    fed = sum(c for _, c in rows)
    per_token = sum(k * n for k, n in _frozen_linear_shapes(model)) \
        + m["h"] * m["dh"]
    linear = 2.0 * fed * per_token * m["layers"]
    attn = sum(4.0 * m["h"] * m["dh"] * counts.keys_seen(p, c)
               for p, c in rows)
    head = 2.0 * sampled * m["d"] * m["vocab"]
    return linear + attn * m["layers"] + head


def _frozen_readings(run, model):
    layers = _frozen_dims(model)["layers"]
    attn = matmul = 0.0
    for tk in run.window.ticks:
        fl = by = 0.0
        for p, c in readers.rows(tk):
            f, b = _frozen_attn_row_cost(p, c, model, run.layout)
            fl, by = fl + f, by + b
        if fl or by:
            attn += counts.roofline_s(fl, by, V5E["bf16_flops_per_s"],
                                      V5E["hbm_bytes_per_s"])[0] * layers
        if tk.fed:
            for k, n in _frozen_linear_shapes(model):
                ops, b = counts.int8_matmul_cost(tk.fed, k, n)
                matmul += counts.roofline_s(ops, b, V5E["int8_ops_per_s"],
                                            V5E["hbm_bytes_per_s"])[0] * layers
    red = run.reduced
    ops = sum(_frozen_tick_model_ops(readers.rows(tk), tk.banked, model)
              for tk in run.window.ticks)
    return {"paged_attn_roofline":
            attn / red.kernel_time("paged_attention") * 100.0,
            "int8_matmul_roofline":
            matmul / red.kernel_time("int8_matmul") * 100.0,
            "mfu": ops / red.window_s / V5E["int8_ops_per_s"] * 100.0}


def _synthetic_run(geometry, seed=3, b=64):
    """A window of mixed ticks at B=64: T=1 decode ticks (a few rows idle)
    and T=256 ticks in which one row prefills a chunk beside the decodes."""
    rng = np.random.default_rng(seed)
    ticks = []
    for i in range(40):
        pos0 = rng.integers(16, 2000, b)
        cnt = (rng.random(b) > 0.1).astype(np.int64)
        if i % 5 == 0:
            j = rng.integers(b)
            cnt[j] = 0
            cnt[j] = 256 - cnt.sum()
        ticks.append(driver.Tick(i, i + 1, int(cnt.sum()),
                                 int((cnt == 1).sum()), pos0, cnt))
    red = trace.Reduced(0.0, 40.0, 36.0, {"paged_attention": 3.25,
                                          "int8_matmul": 0.75},
                        [(0.0, 36.0)], [])
    return SimpleNamespace(geometry=geometry, layout=W8A8, precision="w8a8",
                           peaks=V5E, window=SimpleNamespace(ticks=ticks),
                           reduced=red, notes={})


def test_opt_readings_equal_the_formulas_before_the_geometry():
    run = _synthetic_run(OPT125)
    assert max(tk.counts.max() for tk in run.window.ticks) > 1
    got = {name: getattr(readers, name)(run) for name in
           ("paged_attn_roofline", "int8_matmul_roofline", "mfu")}
    assert got == _frozen_readings(run, A_CONFIG["model"])


def test_opt_program_config_is_the_one_run_built():
    sys.path.insert(0, str(BENCH.parent / "src"))
    from repro.configs import apply_method, paper_models, to_bf16
    want = to_bf16(apply_method(paper_models.opt_125m(), "gated_attention",
                                pi_init=0.5, gate_kind="linear"))
    assert opt_program.program_config(A_CONFIG) == want
    wider = json.loads(json.dumps(A_CONFIG))
    wider["model"]["ffn_dim"] = 4096
    with pytest.raises(ValueError):
        opt_program.program_config(wider)


def _attn_linears(d, h, hkv, dh):
    return [counts.Linear(d, h * dh), counts.Linear(d, hkv * dh),
            counts.Linear(d, hkv * dh), counts.Linear(h * dh, d)]


def test_windowed_layer_reads_only_its_window():
    full = counts.Layer(_attn_linears(2048, 32, 4, 128), 32, 4, 128)
    win = counts.Layer(full.linears, 32, 4, 128, window=2048)
    # a decode at position 5000 reads 2048 keys, against 5001 unwindowed
    flops, nbytes = counts.attn_row_cost(5000, 1, win, W8A8)
    assert flops == 4 * 32 * 128 * 2048
    assert nbytes == 2048 * 4 * 128 * 2 + 2048 * 2 * 4 + 32 * 2 * 128 * 4
    assert counts.attn_row_cost(5000, 1, full, W8A8)[0] == 4 * 32 * 128 * 5001
    # a chunk of 100 at 2000: 48 queries see 2001..2048 keys, 52 see 2048,
    # and the row reads positions 0..2099
    keys = sum(range(2001, 2049)) + 52 * 2048
    assert counts.keys_seen(2000, 100, 2048) == keys
    flops, nbytes = counts.attn_row_cost(2000, 100, win, W8A8)
    assert flops == 4 * 32 * 128 * keys
    assert nbytes == 2100 * 4 * 128 * 2 + 2100 * 2 * 4 + 100 * 32 * 2 * 128 * 4
    # inside the window nothing is clipped
    assert counts.attn_row_cost(100, 4, win, W8A8) == \
        counts.attn_row_cost(100, 4, full, W8A8)
    geo = counts.Geometry([win, win, win, full], 2048, 1000)
    attn = 3 * 4 * 32 * 128 * 2048 + 4 * 32 * 128 * 5001
    linear = 2 * 4 * (2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048)
    assert counts.tick_model_ops([(5000, 1)], 0, geo) == linear + attn


def test_gqa_with_head_dim_not_d_over_h():
    # 32 query heads and 4 KV heads of 128 at d = 2048 (d / h would be 64)
    layer = counts.Layer(_attn_linears(2048, 32, 4, 128), 32, 4, 128)
    assert [(lin.k, lin.n) for lin in layer.linears] == \
        [(2048, 4096), (2048, 512), (2048, 512), (4096, 2048)]
    flops, nbytes = counts.attn_row_cost(1000, 1, layer, W8A8)
    assert flops == 4 * 32 * 128 * 1001
    # K and V of 4 heads only; queries and outputs of 32, no gate
    assert nbytes == 1001 * 4 * 128 * 2 + 1001 * 2 * 4 + 32 * 2 * 128 * 4
    geo = counts.Geometry([layer], 2048, 1000)
    macs = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048
    assert counts.tick_model_ops([(1000, 1), (0, 3)], 0, geo) == \
        2 * 4 * macs + 4 * 32 * 128 * (1001 + 6)


def test_routed_expert_counts_in_mfu_not_in_int8_matmul():
    d, share = 2048, 8 * 8 / 128        # top-8 of 128 experts, 8 held here
    dense = _attn_linears(d, 32, 4, 128) + [counts.Linear(d, 1024),
                                            counts.Linear(d, 1024),
                                            counts.Linear(1024, d)]
    routed = [counts.Linear(d, 1024, share), counts.Linear(d, 1024, share),
              counts.Linear(1024, d, share)]
    moe = counts.Geometry([counts.Layer(dense + routed, 32, 4, 128)] * 2, d,
                          1000)
    base = counts.Geometry([counts.Layer(dense, 32, 4, 128)] * 2, d, 1000)
    rows = [(10, 1), (200, 5)]
    extra = counts.tick_model_ops(rows, 0, moe) - \
        counts.tick_model_ops(rows, 0, base)
    assert extra == 2 * 6 * 0.5 * (3 * d * 1024) * 2
    run_moe, run_base = _synthetic_run(moe), _synthetic_run(base)
    assert readers.int8_matmul_roofline(run_moe) == \
        readers.int8_matmul_roofline(run_base)
    assert readers.mfu(run_moe) > readers.mfu(run_base)


def test_untied_head_on_a_sliced_vocabulary():
    # a 200,192-row head over 4 chips: this chip's head holds 50,048 rows
    layer = counts.Layer(_attn_linears(2048, 32, 4, 128), 32, 4, 128)
    geo = counts.Geometry([layer], 2048, 200192 // 4)
    rows = [(10, 1), (20, 1), (30, 1), (0, 7)]
    assert counts.tick_model_ops(rows, 3, geo) - \
        counts.tick_model_ops(rows, 0, geo) == 2 * 3 * 2048 * 50048
    assert counts.tick_model_ops([], 2, geo) == 2 * 2 * 2048 * 50048


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


# ------------------------------------------------------------------ check
def _rows_at_max_len(req, max_len):
    """``check._rows`` as it was: every sequence padded to ``max_len``."""
    p, o = np.asarray(req.prompt, np.int32), np.asarray(req.output, np.int32)
    seq = np.zeros(max_len, np.int32)
    fed = np.concatenate([p, o[:-1]])
    seq[:len(fed)] = fed
    n = 1 << (len(o) - 1).bit_length()
    at = np.full(n, len(p) - 2 + len(o), np.int32)
    at[:len(o)] = np.arange(len(p) - 1, len(p) - 1 + len(o), dtype=np.int32)
    return seq, at, o


def test_compare_at_the_request_own_length(monkeypatch):
    """Gaps of the reference run on each sequence padded to its own power
    of two equal those padded to ``max_len``, for the served tokens and
    the control alike."""
    import jax
    import tiny
    from opt_gated import logits_fn, make_params
    model = tiny.MODEL
    params = make_params(model, A_CONFIG["init"], 5, jax.numpy.float32)
    rng = np.random.default_rng(11)
    reqs = [SimpleNamespace(prompt=rng.integers(0, 2048, lp),
                            output=rng.integers(0, 2048, lo))
            for lp, lo in ((20, 13), (33, 1), (90, 40), (5, 27))]
    assert [len(check._rows(r, 256)[0]) for r in reqs] == [32, 64, 256, 32]
    assert len(check._rows(reqs[1], 48)[0]) == 48
    ref, ctrl = logits_fn(model), logits_fn(model, bits=4)
    own = check.gaps(reqs, ref, params, 256, control=ctrl)
    monkeypatch.setattr(check, "_rows", _rows_at_max_len)
    padded = check.gaps(reqs, ref, params, 256, control=ctrl)
    for key in ("served", "control"):
        assert own[key].shape == padded[key].shape == (81,)
        np.testing.assert_allclose(own[key], padded[key], rtol=0, atol=1e-5)
