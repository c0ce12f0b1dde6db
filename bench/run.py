"""The chip benchmark: one cell, one seed, one process.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Reads the cell from ``BENCHMARK.json``; its configuration from the file the
entry names, its traffic mix from ``bench/traffic/<traffic>.json`` and each
metric's reader from ``bench/metrics/<metric>.py``, so a new cell, mix,
configuration or metric is new files and entries, never an edit here.

Beside the configuration file lie two modules it names. Its program module
(``program.module``) has ``program_config(config)``, the program's model
config checked against the file's published widths, and
``geometry(config)``, the ``benchlib.counts.Geometry`` that the roofline and
``mfu`` readers count with. Its reference (``reference``) has
``make_params(model, init, seed, dtype)`` and ``logits_fn(model, bits=None)``
and imports nothing of the program. A configuration of another model family
joins by adding, with no file under ``bench/`` edited:

- the configuration JSON in ``bench/configs/`` (``source``, ``model``,
  ``reduced``, ``assumed``, ``departures``, ``program``, ``reference``,
  ``init``, ``precision``, ``engine``, ``layout``, ``control``, ``limits``);
- its program module and its reference, beside it;
- its traffic JSON in ``bench/traffic/``, if the mix is new;
- its ``configs`` and ``workloads`` entries in ``BENCHMARK.json``, and the
  new cell's name in the ``workloads`` list of each end-to-end and
  per-layer metric that applies to it;
- a reader in ``bench/metrics/`` for each metric of what the family adds.

Set-up (counted in ``setup_s`` from process start): weights drawn from the
seed on the device, the engine built (W8A8 calibration included), every
tick program the window can reach compiled or loaded from the persistent
cache, and the traffic run to steady state. Then the window measures for
``--seconds``. With ``--trace 1`` the window is traced and the per-layer
metrics are read from the trace; with ``--trace 0`` the end-to-end ones.
After the window the engine is freed and a sample of the finished requests
is compared with the plain float32 reference (``benchlib.check``).

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), ending with ``checks``: each number compared beside its limit.
The same numbers are the last lines of standard error. Without a TPU, or
with fewer chips than the cell asks for, it exits 1 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from benchlib import check, counts, driver, trace, traffic  # noqa: E402
from benchlib.peaks import UnknownDevice, peaks_for  # noqa: E402

TRACE_DIR = ".bench_trace"


class NoAccelerator(RuntimeError):
    pass


def check_devices(jax, chips: int):
    """The devices this cell runs on: ``chips`` TPUs, or NoAccelerator."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX platform is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX has {len(devs)}")
    return devs[:chips]


@dataclasses.dataclass
class Spec:
    root: Path
    bench: dict
    cell: dict
    config: dict
    mix: dict

    @staticmethod
    def load(root: Path, workload: str) -> "Spec":
        bench = json.loads((root / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
        cell = cells[workload]
        entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
        config = json.loads((root / entry["file"]).read_text())
        mix = traffic.load_mix(root / "bench" / "traffic" /
                               f"{cell['traffic']}.json")
        return Spec(root, bench, cell, config, mix)

    def metrics(self, traced: bool) -> list:
        """This cell's metric entries: its per-layer ones when traced, its
        end-to-end ones otherwise."""
        group = self.bench["per_layer" if traced else "end_to_end"]
        name = self.cell["name"]
        e2e = {m["name"] for m in self.bench["end_to_end"]
               if name in m.get("workloads", [name])}
        return [m for m in group if name in m.get("workloads", [name])
                and (not traced or "workloads" in m or m["moves"] in e2e)]

    def reader(self, metric: str):
        path = self.root / "bench" / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + metric.replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


@dataclasses.dataclass
class Run:
    """What the metric readers read."""
    geometry: counts.Geometry
    layout: dict
    precision: str
    peaks: dict
    setup_s: float
    window: driver.Window
    reduced: Optional[trace.Reduced]
    notes: dict


def use_compile_cache(jax, checkout: Path) -> None:
    """JAX's persistent compile cache at the fixed ``<checkout>/.jax_cache``,
    unbounded, keeping every program: a cell's tick programs then load from
    it in every run after the first. A size cap from the environment would
    let a cell's programs evict each other, so it is lifted here."""
    jax.config.update("jax_compilation_cache_dir", str(checkout / ".jax_cache"))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


class CompileCounter:
    """Counts JAX's traces, backend compiles and persistent-cache hits. A
    backend compile that finds its program in the persistent cache counts
    as a compile and as a cache load."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/backend_compile_duration": "compiles",
              "/jax/compilation_cache/cache_retrieval_time_sec": "cache_loads"}

    def __init__(self, jax):
        self.n = {v: 0 for v in self.EVENTS.values()}
        self.compiles = []

        def on(event, secs, **kw):
            if event in self.EVENTS:
                kind = self.EVENTS[event]
                self.n[kind] += 1
                if kind == "compiles":
                    self.compiles.append((kw.get("fun_name"), secs))
        jax.monitoring.register_event_duration_secs_listener(on)

    def snapshot(self) -> dict:
        return dict(self.n, at=len(self.compiles))

    def between(self, a: dict, b: dict) -> dict:
        """Backend compiles between two snapshots, by program name: how
        many and their seconds."""
        out: dict = {}
        for name, secs in self.compiles[a["at"]:b["at"]]:
            n, s = out.get(name, (0, 0.0))
            out[name] = (n + 1, s + secs)
        return out


def check_layout(jax, params, cfg) -> None:
    """The benchmark's weights have exactly the program's layout."""
    from repro.models import model_init

    want = jax.eval_shape(lambda: model_init(jax.random.PRNGKey(0), cfg))
    shape = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: (a.shape, str(a.dtype)), t)
    if shape(want) != shape(params):
        raise ValueError("benchmark weights do not match the program layout")


def run_cell(spec: Spec, seed: int, seconds: float, traced: bool,
             devices, peaks: dict, control: bool = False) -> dict:
    """Set up, run the window and compare; with ``control`` also judge the
    control (the reference in the precision below the configuration's) on
    the same served tokens by the same limit, under ``control``."""
    import jax
    import jax.numpy as jnp
    from repro.quant import QConfig
    from repro.serving import ContinuousBatcher, Request

    use_compile_cache(jax, BENCH.parent)
    counter = CompileCounter(jax)
    phases = {"imports": time.perf_counter() - T_START}
    config, mix = spec.config, spec.mix
    prog_mod = _load_beside(spec, config["program"]["module"])
    ref_mod = _load_beside(spec, config["reference"])
    cfg = prog_mod.program_config(config)
    params = ref_mod.make_params(config["model"], config["init"],
                                 config["init"]["seed"], jnp.bfloat16)
    check_layout(jax, params, cfg)
    phases["weights"] = time.perf_counter() - T_START
    eng_kw = dict(config["engine"])
    batch = eng_kw["batch_size"]
    engine = ContinuousBatcher(
        params, cfg, paged=True,
        qconfig=QConfig() if config["precision"] == "w8a8" else None,
        **eng_kw)
    phases["engine"] = time.perf_counter() - T_START
    client = driver.Client(engine, Request, time.perf_counter)
    draws = traffic.stream(mix, seed, config["model"]["vocab_size"])
    driver.warm_shapes(engine, batch, [
        (t, w) for w in mix["warm_widths"]
        for t in driver.pow2_upto(eng_kw["token_budget"])])
    phases["warm_shapes"] = time.perf_counter() - T_START
    warmed = counter.snapshot()
    trace_dir = spec.root / TRACE_DIR / spec.cell["name"]
    marks = {}

    def on_open():
        marks["setup_s"] = time.perf_counter() - T_START
        marks["open"] = counter.snapshot()
        if traced:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(trace_dir))

    def on_close():
        if traced:
            jax.profiler.stop_trace()
        marks["close"] = counter.snapshot()

    window = driver.closed_loop(client, mix, draws, seconds, batch, on_open,
                                on_close)
    phases["fill"] = marks["setup_s"]
    # requests in flight at some time in the window
    alive = [t for t in client.tracked.values()
             if t.submitted < window.t_close and
             not (t.times and len(t.times) == t.req.max_new_tokens
                  and t.times[-1] < window.t_open)]
    failed_uids = {r.uid for r in engine.failed}
    attempted = len(alive)
    failed = sum(1 for t in alive if t.req.uid in failed_uids)
    dev = devices[0]
    mem = dev.memory_stats() or {}
    finished = [r for r in engine.done if r.status == "done"]

    reduced = None
    if traced:
        tr = trace.load(trace_dir)
        lo_hi = trace.span_window(tr, "bench.window")
        reduced = trace.reduce(tr, *lo_hi) if lo_hi else None
        shutil.rmtree(trace_dir, ignore_errors=True)

    run = Run(prog_mod.geometry(config), config["layout"],
              config["precision"], peaks, marks["setup_s"], window, reduced,
              {})
    metrics = {}
    for m in spec.metrics(traced):
        v = spec.reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # free the program's state before the reference runs
    del engine, client
    gc.collect()
    t_check = time.perf_counter()
    sample = check.sample(finished, seed, **mix["check"])
    params32 = jax.jit(lambda p: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), p))(params)
    del params
    ctrl = ref_mod.logits_fn(config["model"], bits=config["control"]["bits"]) \
        if control else None
    read = check.gaps(sample, ref_mod.logits_fn(config["model"]), params32,
                      eng_kw["max_len"], control=ctrl)
    limit = config["limits"]["max_gap"]
    judged = check.verdict(read["served"], limit)
    checks = {"max_gap": {"value": judged["max_gap"], "limit": limit},
              "compared_tokens": {"value": int(read["served"].size),
                                  "limit": 1}}
    check_s = time.perf_counter() - t_check

    opened, closed = marks["open"], marks["close"]
    info = {
        "setup_s": marks["setup_s"],
        "setup_phases_s": phases,
        "window_s": window.seconds, "ticks": len(window.ticks),
        "in_window": {k: closed[k] - opened[k] for k in closed if k != "at"},
        "in_window_by_name": counter.between(opened, closed),
        "in_setup": {k: v for k, v in opened.items() if k != "at"},
        "in_fill_by_name": counter.between(warmed, opened),
        "in_setup_by_name": counter.between({"at": 0}, opened),
        "requests_finished": len(finished), "compared_requests": len(sample),
        "compared_longest": max((len(r.prompt) + len(r.output)
                                 for r in sample), default=0),
        "check_s": check_s,
    }
    info.update(run.notes)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}
    result = {"correct": judged["correct"], "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if traced and reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = trace.breakdown(reduced)
    result["checks"] = checks
    out = {"info": info, "result": result}
    if control:
        out["control"] = check.verdict(read["control"], limit)
    return out


def _load_beside(spec: Spec, name: str):
    """The module ``<name>.py`` beside the cell's configuration file: its
    program module or its reference."""
    entry = {c["name"]: c for c in spec.bench["configs"]}[spec.cell["config"]]
    path = (spec.root / entry["file"]).parent / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location("bench_config_" + name,
                                                      path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def main(argv=None, root: Path = BENCH.parent) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = Spec.load(Path(root), args.workload)
    import jax
    try:
        devices = check_devices(jax, spec.cell["chips"])
        peaks = peaks_for(devices[0].device_kind)
    except (NoAccelerator, UnknownDevice) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    src = BENCH.parent / "src"
    if not (src / "repro").is_dir():
        print(f"bench: the program is not at {src}", file=sys.stderr)
        return 1
    sys.path.insert(0, str(src))
    out = run_cell(spec, args.seed, args.seconds, bool(args.trace), devices,
                   peaks)
    print("info " + json.dumps(out["info"]), flush=True)
    for name, c in out["result"]["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out["result"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
