"""Serving-quality harness for end-to-end INT8 serving — the paper's
Table 2 story, live: greedy outputs of the W8A8 + int8-KV batcher vs the
fp model, across vanilla / clipped-softmax / gated-attention and
dense / paged (gather oracle + Pallas kernel) backends.

Metric design (why trained models + injected outliers):

* Greedy token agreement on RANDOM-INIT models is a coin flip — logits
  are flat, so fp-vs-int8 argmax agreement sits near chance for every
  config and the paper's contrast is invisible. The fixture therefore
  TRAINS each tiny model for a few hundred steps on the synthetic Markov
  chain; the chain's top-1 transition is deterministic, so a converged
  model has decisive argmax margins and an outlier-free model survives
  W8A8 + int8-KV serving with agreement ~1.0.
* Tiny models trained for seconds never GROW the paper's outliers, so the
  "vanilla at scale" condition is simulated structurally: a few fc1
  output channels are amplified by M with the matching fc2 rows scaled by
  1/M. Since relu(M·x) = M·relu(x) for M > 0 the fp function is exactly
  unchanged — but the per-tensor activation range at the fc2 input
  explodes by ~M, which is precisely the outlier→range failure chain
  (PAPER.md Fig. 1; Wei et al., 2022). The amplified channels vary per
  token (unlike a scaled embedding column, whose constant residual
  direction acts as an argmax attractor and paradoxically *stabilizes*
  int8 agreement), so the injection degrades serving the way real
  outliers do.
* The int8 engine serves its requests free-running (prefill chunks, then
  decode ticks over the int8 pool), and each served token is scored
  against the fp model's greedy token on the same prefix — the tokens
  the int8 engine itself served before it. Where the chain branches, the
  fp top-2 logits can be a near-tie that W8A8 flips (the calibrated
  fake-quant model flips it as well); scored against the fp engine's own
  continuation, one such flip would count every later token as wrong.

Also here: bitwise invariance of int8-KV serving to chunk size, slot
assignment, and preemption-resume — same oracles as test_chunked_prefill,
now with quantize-on-write pools (each token's int8 code + scale are a
pure function of (value, logical position); see quant.kv_cache).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import apply_method
from repro.configs.paper_models import opt_tiny
from repro.data.synthetic import SyntheticLM, SyntheticLMConfig
from repro.models import model_apply
from repro.optim.adamw import AdamWConfig
from repro.quant import QConfig
from repro.serving.scheduler import ContinuousBatcher, Request
from repro.train.step import TrainTask, init_train_state, make_train_step

VOCAB, SEQ = 64, 32
TRAIN_STEPS = 400
METHODS = ("vanilla", "clipped_softmax", "gated_attention")
# thresholds (measured: clean agreement 1.0 for every method x backend;
# outlier-vanilla 0.0 at M=300 x 2 channels — margins are wide on purpose)
CLEAN_FLOOR = 0.9
OUTLIER_CEIL = 0.6
QC = QConfig()


def _cfg(method, backend="gather"):
    cfg = opt_tiny(vocab=VOCAB, seq_len=SEQ)
    cfg = dataclasses.replace(cfg, n_layers=2, d_model=64, n_heads=2,
                              n_kv_heads=2, d_head=32, d_ff=256,
                              paged_backend=backend)
    if method == "clipped_softmax":
        return apply_method(cfg, method, alpha=4.0)
    return apply_method(cfg, method)


def _train(method):
    cfg = _cfg(method)
    task = TrainTask(cfg=cfg, optimizer=AdamWConfig(lr=1e-3))
    data = SyntheticLM(SyntheticLMConfig(vocab_size=VOCAB, seq_len=SEQ,
                                         batch_size=32, seed=0, branching=8))
    state = init_train_state(jax.random.PRNGKey(0), task)
    step_fn = jax.jit(make_train_step(task), donate_argnums=(0,))
    for i in range(TRAIN_STEPS):
        batch = jax.tree_util.tree_map(jnp.asarray, data.batch(i))
        state, _ = step_fn(state, batch)
    return state.params


def _inject_outliers(params, channels=(3, 11), m=300.0):
    """Function-preserving channel amplification (see module docstring)."""
    broken = jax.tree_util.tree_map(jnp.asarray, params)
    for layer in broken["layers"]:
        blk = layer["b0"]
        for c in channels:
            blk["mlp"]["up"]["w"] = blk["mlp"]["up"]["w"].at[:, c].mul(m)
            blk["mlp"]["up"]["b"] = blk["mlp"]["up"]["b"].at[c].mul(m)
            blk["mlp"]["down"]["w"] = blk["mlp"]["down"]["w"].at[c, :].mul(1.0 / m)
    return broken


@pytest.fixture(scope="module")
def trained():
    """method -> trained params (+ 'vanilla_outliers' variant)."""
    models = {m: _train(m) for m in METHODS}
    models["vanilla_outliers"] = _inject_outliers(models["vanilla"])
    return models


@pytest.fixture(scope="module")
def prompts():
    data = SyntheticLM(SyntheticLMConfig(vocab_size=VOCAB, seq_len=SEQ,
                                         batch_size=32, seed=0, branching=8))
    batch = data.batch(999)
    return [batch["tokens"][i][:12].astype(np.int32) for i in range(6)]


def _run_engine(params, cfg, prompts, qconfig=None, paged=True, **kw):
    b = ContinuousBatcher(params, cfg, batch_size=4, max_len=64, block_size=8,
                          paged=paged, qconfig=qconfig, **kw)
    for i, p in enumerate(prompts):
        b.submit(Request(uid=i, prompt=p, max_new_tokens=16))
    return {r.uid: np.asarray(r.output) for r in b.run()}


def _agreement(fp, q8):
    tot = match = 0
    for uid in fp:
        for x, y in zip(fp[uid], q8[uid]):
            tot += 1
            match += int(x == y)
    return match / max(tot, 1)


@pytest.fixture(scope="module")
def fp_choice(trained, prompts):
    """(model name, served outputs) -> the fp model's greedy token at every
    served position, given the served prefix: one cache-free forward of
    prompt + output per request."""
    def choice(name, outputs):
        params = trained[name]
        cfg = _cfg("vanilla" if name.startswith("vanilla") else name)
        res = {}
        for uid, out in outputs.items():
            p = prompts[uid]
            seq = jnp.asarray(np.concatenate([p, out[:-1]]))[None]
            logits = model_apply(params, cfg, {"tokens": seq})[0][0]
            res[uid] = np.asarray(jnp.argmax(logits[len(p) - 1:], axis=-1))
        return res
    return choice


class TestTable2Agreement:
    """Outlier-free configs survive full INT8 serving; outliers break it."""

    @pytest.mark.parametrize("method", METHODS)
    @pytest.mark.parametrize("paged", [True, False], ids=["paged", "dense"])
    def test_clean_models_agree_with_fp(self, trained, prompts, fp_choice,
                                        method, paged):
        q8 = _run_engine(trained[method], _cfg(method), prompts,
                         qconfig=QC, paged=paged)
        ag = _agreement(fp_choice(method, q8), q8)
        assert ag >= CLEAN_FLOOR, (method, paged, ag)

    def test_outlier_vanilla_degrades_paged(self, trained, prompts, fp_choice):
        """The headline contrast: same fp function as clean vanilla, but
        int8 serving collapses once per-tensor ranges carry outliers —
        while clipped/gated (which never grow them) stay at the floor."""
        q8 = _run_engine(trained["vanilla_outliers"], _cfg("vanilla"),
                         prompts, qconfig=QC, paged=True)
        bad = _agreement(fp_choice("vanilla_outliers", q8), q8)
        assert bad <= OUTLIER_CEIL, bad
        for method in ("clipped_softmax", "gated_attention"):
            q8 = _run_engine(trained[method], _cfg(method), prompts,
                             qconfig=QC, paged=True)
            good = _agreement(fp_choice(method, q8), q8)
            assert good >= CLEAN_FLOOR > bad, (method, good, bad)

    def test_kernel_backend_clean_and_outlier(self, trained, prompts,
                                              fp_choice):
        """Same thresholds on the Pallas paged kernel (interpret mode):
        the per-block dequant epilogue must neither lose the clean models'
        agreement nor mask the outlier failure."""
        q8 = _run_engine(trained["clipped_softmax"],
                         _cfg("clipped_softmax", backend="kernel"),
                         prompts, qconfig=QC, paged=True)
        assert _agreement(fp_choice("clipped_softmax", q8), q8) >= CLEAN_FLOOR
        q8_bad = _run_engine(trained["vanilla_outliers"],
                             _cfg("vanilla", backend="kernel"),
                             prompts, qconfig=QC, paged=True)
        assert _agreement(fp_choice("vanilla_outliers", q8_bad),
                          q8_bad) <= OUTLIER_CEIL

    @pytest.mark.slow
    def test_kernel_backend_full_matrix(self, trained, prompts, fp_choice):
        for method in METHODS:
            q8 = _run_engine(trained[method], _cfg(method, backend="kernel"),
                             prompts, qconfig=QC, paged=True)
            assert _agreement(fp_choice(method, q8), q8) >= CLEAN_FLOOR, method


class TestInt8KVInvariance:
    """Bitwise invariance of int8-KV serving (quantize-on-write pools) to
    scheduling accidents — the same oracles test_chunked_prefill runs for
    the fp engine. Random-init params suffice: equality is bitwise, not
    statistical. kv_int8 is forced on WITHOUT W8A8 first (isolating the
    pool), then the full int8 stack is checked for chunk invariance."""

    @pytest.fixture(scope="class")
    def setup(self):
        from repro.models import model_init
        cfg = _cfg("gated_attention")
        params = model_init(jax.random.PRNGKey(1), cfg)
        rng = np.random.default_rng(3)
        prompts = [rng.integers(4, VOCAB, size=n).astype(np.int32)
                   for n in (11, 5, 17, 8)]
        return cfg, params, prompts

    def _run(self, cfg, params, prompts, qconfig=None, **kw):
        b = ContinuousBatcher(params, cfg, max_len=32, block_size=4,
                              paged=True, kv_int8=True, qconfig=qconfig, **kw)
        for i, p in enumerate(prompts):
            b.submit(Request(uid=i, prompt=p, max_new_tokens=8))
        return {r.uid: np.asarray(r.output) for r in b.run()}

    def test_chunk_size_invariance(self, setup):
        cfg, params, prompts = setup
        ref = self._run(cfg, params, prompts, batch_size=4)
        for kw in (dict(token_budget=5), dict(token_budget=7),
                   dict(prefill_chunk=3)):
            out = self._run(cfg, params, prompts, batch_size=4, **kw)
            for uid in ref:
                np.testing.assert_array_equal(out[uid], ref[uid],
                                              err_msg=f"{kw} uid={uid}")

    def test_slot_assignment_invariance(self, setup):
        """Fewer slots than requests => different rows/physical blocks per
        request; outputs must not move (scale vectors ride the pool, not
        the slot)."""
        cfg, params, prompts = setup
        ref = self._run(cfg, params, prompts, batch_size=4)
        for b in (1, 2):
            out = self._run(cfg, params, prompts, batch_size=b)
            for uid in ref:
                np.testing.assert_array_equal(out[uid], ref[uid],
                                              err_msg=f"B={b} uid={uid}")

    def test_preemption_resume_invariance(self, setup):
        """A pool too small to hold every row forces preempt + recompute-
        resume; re-quantizing the recomputed prefix must reproduce the
        exact bits (one quantization per (value, position))."""
        cfg, params, prompts = setup
        roomy = self._run(cfg, params, prompts, batch_size=4)
        tight = self._run(cfg, params, prompts, batch_size=4, num_blocks=10)
        for uid in roomy:
            np.testing.assert_array_equal(tight[uid], roomy[uid],
                                          err_msg=f"uid={uid}")

    def test_full_int8_chunk_invariance(self, setup):
        """W8A8 + int8 KV together: calibration happens once at engine
        construction from fixed synthetic batches, so two engines over the
        same params are identical quantized programs and chunking still
        cannot move outputs."""
        cfg, params, prompts = setup
        ref = self._run(cfg, params, prompts, batch_size=4, qconfig=QC)
        out = self._run(cfg, params, prompts, batch_size=4, qconfig=QC,
                        token_budget=6)
        for uid in ref:
            np.testing.assert_array_equal(out[uid], ref[uid],
                                          err_msg=f"uid={uid}")
