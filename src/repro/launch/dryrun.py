import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("REPRO_XLA_FLAGS_EXTRA", "") +
    " --xla_force_host_platform_device_count=512"
)
"""Multi-pod dry-run: AOT lower + compile every (architecture x input shape)
cell on the production meshes, and extract roofline terms.

MUST be the first importer of jax in the process (XLA_FLAGS above is set
before any other import — jax locks the device count at first init).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch deepseek-67b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all          # every cell
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh multi

Roofline-term fidelity: XLA's ``cost_analysis`` counts a while-loop (scan)
body ONCE regardless of trip count, so a scanned-layers model under-reports
FLOPs/bytes/collectives by ~n_groups x. The full scanned config is still
compiled — that is the pass/fail artifact and the source of
``memory_analysis`` — but the roofline terms come from a two-point
extrapolation over UNROLLED reduced-depth twins (1 group + tail, 2 groups +
tail):  total(G) = (2*c1 - c2) + G*(c2 - c1), exact for homogeneous groups.

Results land in experiments/dryrun/<cell>.json — EXPERIMENTS.md §Dry-run
and §Roofline read from those.
"""
import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPES, apply_method, cache_specs, get_arch, input_specs, list_archs
from repro.distributed.sharding import batch_specs, cache_specs_tree, tree_param_specs
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import HBM_BW, ICI_BW, PEAK_FLOPS, analyze, model_flops_infer, model_flops_train, parse_collectives
from repro.models.transformer import ModelConfig, model_init
from repro.nn.module import flatten_params
from repro.optim.adamw import AdamWConfig
from repro.train.step import TrainTask, init_train_state, make_decode_step, make_prefill_step, make_train_step

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def active_param_count(cfg: ModelConfig) -> int:
    """Parameter count weighted by MoE activation (top_k/n_experts) —
    feeds MODEL_FLOPS = 6*N_active*D."""
    shapes = jax.eval_shape(lambda: model_init(jax.random.PRNGKey(0), cfg))
    total = 0
    for path, leaf in flatten_params(shapes):
        n = 1
        for d in leaf.shape:
            n *= int(d)
        if "/moe/w_" in f"/{path}":
            n = int(n * cfg.moe.top_k / cfg.moe.n_experts)
        total += n
    return total


def param_count_full(cfg: ModelConfig) -> int:
    shapes = jax.eval_shape(lambda: model_init(jax.random.PRNGKey(0), cfg))
    total = 0
    for _, leaf in flatten_params(shapes):
        n = 1
        for d in leaf.shape:
            n *= int(d)
        total += n
    return total


def _ns(mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def build_lowered(cfg: ModelConfig, shape, mesh, profile: str,
                  microbatch: int = 1):
    """Construct the jitted step + ShapeDtypeStruct args for one cell and
    return the lowered module."""
    batch = input_specs(cfg, shape)
    if shape.step == "train":
        task = TrainTask(cfg=cfg, loss_kind="clm" if cfg.causal else "frames",
                         optimizer=AdamWConfig(), microbatch=microbatch)
        state_shapes = jax.eval_shape(
            lambda: init_train_state(jax.random.PRNGKey(0), task))
        state_specs = tree_param_specs(state_shapes, profile, mesh)
        bspecs = batch_specs(batch, mesh)
        jitted = jax.jit(make_train_step(task),
                         in_shardings=(_ns(mesh, state_specs), _ns(mesh, bspecs)),
                         out_shardings=(_ns(mesh, state_specs), None),
                         donate_argnums=(0,))
        with jax.sharding.set_mesh(mesh):
            return jitted.lower(state_shapes, batch)
    params_shapes = jax.eval_shape(lambda: model_init(jax.random.PRNGKey(0), cfg))
    pspecs = tree_param_specs(params_shapes, profile, mesh)
    if shape.step == "prefill":
        # "tp_seq": context parallelism — sequence over the model axis,
        # weights tp_only; MLPs become token-parallel (no activation AR),
        # attention gathers KV per layer instead.
        bspecs = batch_specs(batch, mesh, shard_seq=profile == "tp_seq",
                             seq_axis="model")
        jitted = jax.jit(make_prefill_step(cfg),
                         in_shardings=(_ns(mesh, pspecs), _ns(mesh, bspecs)))
        with jax.sharding.set_mesh(mesh):
            return jitted.lower(params_shapes, batch)
    # decode
    cache_shapes = cache_specs(cfg, shape)
    cspecs = cache_specs_tree(cache_shapes, mesh, cfg, shape.global_batch)
    tok = batch["tokens"]
    n_dp = 1
    for ax in mesh.axis_names:
        if ax != "model":
            n_dp *= mesh.shape[ax]
    tok_spec = batch_specs({"tokens": tok}, mesh)["tokens"] \
        if shape.global_batch % n_dp == 0 else P()
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    jitted = jax.jit(
        make_decode_step(cfg),
        in_shardings=(_ns(mesh, pspecs), _ns(mesh, cspecs),
                      NamedSharding(mesh, tok_spec), NamedSharding(mesh, P())),
        out_shardings=(None, _ns(mesh, cspecs)),
        donate_argnums=(1,),
    )
    with jax.sharding.set_mesh(mesh):
        return jitted.lower(params_shapes, cache_shapes, tok, pos)


def _cost_triple(compiled) -> Tuple[float, float, float]:
    ca = compiled.cost_analysis()
    colls = parse_collectives(compiled.as_text())
    return (float(ca.get("flops", 0.0)),
            float(ca.get("bytes accessed", 0.0)),
            colls.wire_bytes)


def extrapolated_costs(cfg: ModelConfig, shape, mesh, profile: str
                       ) -> Dict[str, float]:
    """Two-point unrolled extrapolation of (flops, hbm, wire) per device.

    Depths 2 and 3 (not 1 and 2): the 1-group module shows boundary
    effects — XLA hoists/CSEs collectives differently when a tensor is
    used once — which can make the naive slope negative."""
    glen = len(cfg.pattern)
    tail = cfg.n_layers % glen
    g_full = cfg.n_groups
    k_lo, k_hi = (2, 3) if g_full >= 3 else (1, 2)
    cfgs = [dataclasses.replace(cfg, n_layers=glen * k + tail,
                                scan_layers=False)
            for k in (k_lo, k_hi)]
    c_lo = _cost_triple(build_lowered(cfgs[0], shape, mesh, profile).compile())
    c_hi = _cost_triple(build_lowered(cfgs[1], shape, mesh, profile).compile())
    out = {}
    for name, a, b in zip(("flops", "hbm_bytes", "wire_bytes"), c_lo, c_hi):
        per_group = max(b - a, 0.0)
        fixed = max(a - k_lo * per_group, 0.0)
        out[name] = fixed + g_full * per_group
        out[name + "_per_group"] = per_group
    return out


def lower_cell(arch_id: str, shape_name: str, mesh, profile: str = "tp_fsdp",
               method: str = "clipped_softmax", microbatch: int = 1,
               skip_extrapolation: bool = False,
               moe_exec: Optional[str] = None) -> Dict[str, Any]:
    """Lower + compile one cell; return a JSON-serializable report."""
    spec = get_arch(arch_id)
    shape = SHAPES[shape_name]
    why = spec.skipped(shape_name)
    if why is not None:
        return {"arch": arch_id, "shape": shape_name, "status": "skipped",
                "reason": why}

    cfg = apply_method(spec.full(), method)
    cfg = dataclasses.replace(
        cfg, max_seq_len=max(shape.seq_len + 8, cfg.window or 0))
    if moe_exec and cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, exec_mode=moe_exec))
    n_chips = mesh.devices.size
    report: Dict[str, Any] = {
        "arch": arch_id, "shape": shape_name, "mesh": list(mesh.shape.values()),
        "profile": profile, "method": method, "status": "ok",
    }

    t0 = time.time()
    lowered = build_lowered(cfg, shape, mesh, profile, microbatch)
    report["lower_s"] = round(time.time() - t0, 2)
    t1 = time.time()
    compiled = lowered.compile()
    report["compile_s"] = round(time.time() - t1, 2)

    if shape.step == "train":
        n_tokens = shape.global_batch * shape.seq_len
        mf = model_flops_train(active_param_count(cfg), n_tokens)
    elif shape.step == "prefill":
        mf = model_flops_infer(active_param_count(cfg),
                               shape.global_batch * shape.seq_len)
    else:
        mf = model_flops_infer(active_param_count(cfg), shape.global_batch)

    roof = analyze(compiled, n_chips, model_flops_total=mf)
    report["roofline_scanned_raw"] = roof.as_dict()

    if not skip_extrapolation and cfg.scan_layers and cfg.n_groups > 2:
        t2 = time.time()
        ext = extrapolated_costs(cfg, shape, mesh, profile)
        report["extrapolate_s"] = round(time.time() - t2, 2)
        terms = {
            "flops_per_device": ext["flops"],
            "hbm_bytes_per_device": ext["hbm_bytes"],
            "wire_bytes_per_device": ext["wire_bytes"],
            "compute_s": ext["flops"] / PEAK_FLOPS,
            "memory_s": ext["hbm_bytes"] / HBM_BW,
            "collective_s": ext["wire_bytes"] / ICI_BW,
        }
        terms["bottleneck"] = max(
            ("compute", "memory", "collective"),
            key=lambda k: terms[k + "_s" if k != "collective" else "collective_s"])
        terms["model_flops"] = mf / n_chips
        terms["useful_flops_ratio"] = (
            (mf / n_chips) / terms["flops_per_device"]
            if terms["flops_per_device"] else None)
        terms["memory_stats"] = roof.memory_stats
        report["roofline"] = terms
    else:
        report["roofline"] = roof.as_dict()

    report["params_total"] = param_count_full(cfg)
    report["params_active"] = active_param_count(cfg)
    return report


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--profile", default="tp_fsdp")
    ap.add_argument("--method", default="clipped_softmax")
    ap.add_argument("--moe-exec", default=None, choices=[None, "dense", "dispatch"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--fast", action="store_true",
                    help="skip cost extrapolation (pass/fail only)")
    ap.add_argument("--out", default=OUT_DIR)
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    archs = list_archs() if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    n_ok = n_skip = n_fail = 0
    for multi in meshes:
        mesh = make_production_mesh(multi_pod=multi)
        mesh_tag = "2x16x16" if multi else "16x16"
        for arch in archs:
            for shp in shapes:
                tag = f"{arch}__{shp}__{mesh_tag}__{args.profile}"
                path = os.path.join(args.out, tag + ".json")
                try:
                    rep = lower_cell(arch, shp, mesh, args.profile, args.method,
                                     skip_extrapolation=args.fast,
                                     moe_exec=args.moe_exec)
                except Exception as e:  # noqa: BLE001 — report and continue
                    rep = {"arch": arch, "shape": shp, "mesh": mesh_tag,
                           "status": "error", "error": str(e)[:2000],
                           "traceback": traceback.format_exc()[-3000:]}
                rep["mesh_tag"] = mesh_tag
                with open(path, "w") as f:
                    json.dump(rep, f, indent=1)
                st = rep["status"]
                n_ok += st == "ok"
                n_skip += st == "skipped"
                n_fail += st == "error"
                extra = ""
                if st == "ok":
                    r = rep["roofline"]
                    extra = (f"bottleneck={r['bottleneck']} "
                             f"c={r['compute_s']:.3f}s m={r['memory_s']:.3f}s "
                             f"x={r['collective_s']:.3f}s "
                             f"lower={rep['lower_s']}s compile={rep['compile_s']}s")
                elif st == "error":
                    extra = rep["error"].splitlines()[0][:140] if rep["error"] else ""
                print(f"[{st:7s}] {tag} {extra}", flush=True)
    print(f"done: {n_ok} ok, {n_skip} skipped, {n_fail} failed")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
