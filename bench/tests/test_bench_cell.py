"""One cell end to end on the CPU at ``opt_tiny`` size, with the harness's
look for a chip patched inside the test: the last line's keys and metric
names, a mix and a configuration of another family added as files only, a
token altered where it is produced (``correct`` must come out false), the
control failing the limit that the program passes, and the unpatched
command refusing to run off TPU."""
import hashlib
import io
import json
import os
import shutil
import subprocess
import sys
from contextlib import redirect_stdout
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))
sys.path.insert(0, str(BENCH.parent / "src"))

import tiny  # noqa: E402

SEED = 2**31 + 123


@pytest.fixture
def harness(monkeypatch):
    """``run`` with the look for a chip and its peaks patched, and JAX's
    persistent compile cache left as the rest of the suite has it."""
    import run
    from benchlib import peaks
    v5e = peaks.peaks_for("TPU v5 lite")
    monkeypatch.setattr(run, "check_devices",
                        lambda jax, chips: jax.devices()[:chips])
    monkeypatch.setattr(run, "peaks_for", lambda kind: v5e)
    monkeypatch.setattr(run, "use_compile_cache", lambda jax, checkout: None)
    return run


def _main(run, root, cell, trace=0, seconds="1"):
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = run.main(["--workload", cell, "--seed", str(SEED), "--seconds",
                       seconds, "--trace", str(trace)], root=root)
    lines = buf.getvalue().strip().splitlines()
    return rc, lines


def test_cell_prints_the_contract_line(harness, tmp_path):
    root = tiny.make_root(tmp_path)
    rc, lines = _main(harness, root, "tiny.tiny-closed")
    assert rc == 0
    res = json.loads(lines[-1])
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    bench = json.loads((root / "BENCHMARK.json").read_text())
    want = {m["name"] for m in bench["end_to_end"]
            if "tiny.tiny-closed" in m.get("workloads", ["tiny.tiny-closed"])}
    assert set(res["metrics"]) == want
    for m in res["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert res["correct"] is True and res["failed"] == 0
    assert res["checks"]["max_gap"]["value"] <= res["checks"]["max_gap"]["limit"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(res["device"])
    info = json.loads(lines[-2].removeprefix("info "))
    assert info["ticks"] > 0 and "in_window" in info


def test_new_mix_is_files_and_an_entry(harness, tmp_path):
    """A mix the repository does not have, added from a temporary root as a
    data file and a BENCHMARK.json entry: nothing else changes."""
    root = tiny.make_root(tmp_path, mixes={"tiny-closed": tiny.CLOSED,
                                           "tiny-short": tiny.SHORT})
    rc, lines = _main(harness, root, "tiny.tiny-short")
    assert rc == 0
    res = json.loads(lines[-1])
    assert res["correct"] is True
    assert set(res["metrics"]) == {"setup_s", "out_tok_s"}
    info = json.loads(lines[-2].removeprefix("info "))
    assert info["ticks"] > 0 and info["requests_finished"] > 0


def _digests(base: Path) -> dict:
    """SHA-256 of every file under ``base`` but tests and byte code."""
    out = {}
    for p in base.rglob("*"):
        rel = p.relative_to(base)
        if p.is_file() and not {"__pycache__", "tests"} & set(rel.parts):
            out[rel] = hashlib.sha256(p.read_bytes()).hexdigest()
    return out


def test_new_configuration_is_files_and_an_entry(harness, tmp_path,
                                                 monkeypatch):
    """A configuration of another family (RMSNorm, RoPE, GQA 4:1 at a head
    size other than d/h, SwiGLU, an untied head) joins from a temporary
    root as its file, its program module, its reference and entries. Its
    cell runs untraced and traced and is correct. The CPU has no device
    plane, so the traced run is given one: a ``fusion`` and a
    ``paged_attention`` op inside every ``bench.step`` span, for the
    readers of device time to count the new geometry against. No file of
    the repository's ``bench/`` changes."""
    from benchlib import trace
    root = tiny.make_root(tmp_path)
    configs = root / "bench" / "configs"
    for mod in ("rope_gqa", "rope_gqa_program"):
        shutil.copy(BENCH / "tests" / f"{mod}.py", configs / f"{mod}.py")
    (configs / "rope-gqa-tiny.json").write_text(json.dumps(tiny.ROPE_GQA))
    cell = "rope-gqa-tiny.tiny-closed"
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "rope-gqa-tiny", "source": "test",
                             "file": "bench/configs/rope-gqa-tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": cell, "config": "rope-gqa-tiny",
                               "traffic": "tiny-closed", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and m["name"] != "int8_matmul_roofline":
            m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    real_load = trace.load

    def load(path, *a, **k):
        tr = real_load(path, *a, **k)
        tr.device_ops["/device:TPU:0"] = [
            (name, s + f0 * (e - s), s + f1 * (e - s))
            for n, s, e in tr.spans if n == "bench.step"
            for name, f0, f1 in (("fusion", 0.1, 0.3),
                                 ("paged_attention", 0.3, 0.6))]
        return tr
    monkeypatch.setattr(trace, "load", load)

    rc, lines = _main(harness, root, cell)
    assert rc == 0
    res = json.loads(lines[-1])
    assert res["correct"] is True
    assert set(res["metrics"]) == {"setup_s", "out_tok_s"}
    assert res["metrics"]["out_tok_s"]["value"] > 0
    rc, lines = _main(harness, root, cell, trace=1)
    assert rc == 0
    res = json.loads(lines[-1])
    assert res["correct"] is True
    got = {n: m["value"] for n, m in res["metrics"].items()}
    assert {"mfu", "paged_attn_roofline"} <= set(got)
    assert "int8_matmul_roofline" not in got
    assert 0 < got["mfu"] <= 100 and 0 < got["paged_attn_roofline"] <= 100
    repo, got = _digests(BENCH), _digests(root / "bench")
    assert {p: got.get(p) for p in repo} == repo


def test_altered_token_is_not_correct(harness, tmp_path, monkeypatch):
    """The tick's sampled token is altered where it is produced."""
    import jax.numpy as jnp
    from repro.serving import scheduler

    real = scheduler.make_mixed_step

    def broken(*a, **k):
        step = real(*a, **k)

        def call(*args):
            nxt, cache = step(*args)
            return (nxt + 1) % 2048, cache
        call.lower = step.lower
        return call
    monkeypatch.setattr(scheduler, "make_mixed_step", broken)
    root = tiny.make_root(tmp_path)
    rc, lines = _main(harness, root, "tiny.tiny-closed")
    res = json.loads(lines[-1])
    assert rc == 0 and res["correct"] is False
    assert res["checks"]["max_gap"]["value"] > res["checks"]["max_gap"]["limit"]
    del jnp


def test_control_fails_where_the_program_passes(harness, tmp_path):
    """The W8A8 configuration's control, the reference in int4 read on the
    served tokens, is judged not correct by the limit that the program's
    own tokens meet."""
    import control
    root = tiny.make_root(tmp_path, precision="w8a8",
                          limit=tiny.LIMITS["w8a8"])
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = control.main(["--workload", "tiny.tiny-closed", "--seeds",
                           str(SEED), "--seconds", "1"], root=root)
    assert rc == 0
    got = json.loads(buf.getvalue().strip().splitlines()[-1])
    assert got["correct"] is True
    assert got["control_correct"] is False


def test_unpatched_command_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "opt125m-w8a8.longgen-backlog", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr
