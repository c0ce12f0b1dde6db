"""Aggregate the dry-run JSONs into the §Roofline table (reads
experiments/dryrun/*.json written by repro.launch.dryrun)."""
from __future__ import annotations

import glob
import json
import os

DRYRUN_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                          "dryrun")


def load_cells(mesh_tag: str = "16x16"):
    cells = []
    for path in sorted(glob.glob(os.path.join(DRYRUN_DIR, "*.json"))):
        with open(path) as f:
            rep = json.load(f)
        if rep.get("mesh_tag") == mesh_tag:
            rep["_profile"] = rep.get("profile", "tp_fsdp")
            cells.append(rep)
    return cells


def run(print_fn=print, mesh_tag: str = "16x16") -> None:
    cells = load_cells(mesh_tag)
    if not cells:
        print_fn(f"# Roofline — no dry-run results yet (run "
                 f"`python -m repro.launch.dryrun --all`)")
        return
    print_fn(f"# Roofline table — mesh {mesh_tag} (terms in seconds/step, "
             "per-device basis)")
    print_fn("arch,shape,profile,status,compute_s,memory_s,collective_s,"
             "bottleneck,useful_flops_ratio")
    n_ok = 0
    for rep in cells:
        if rep["status"] == "ok":
            r = rep["roofline"]
            ratio = r.get("useful_flops_ratio")
            pr = rep["_profile"]
            ratio_s = f"{ratio:.3f}" if ratio else "n/a"
            print_fn(f"{rep['arch']},{rep['shape']},{pr},ok,"
                     f"{r['compute_s']:.4f},{r['memory_s']:.4f},"
                     f"{r['collective_s']:.4f},{r['bottleneck']},{ratio_s}")
            n_ok += 1
        else:
            reason = rep.get("reason", rep.get("error", ""))[:60].replace(
                ",", ";").replace("\n", " ")
            print_fn(f"{rep['arch']},{rep['shape']},{rep.get('_profile','')},"
                     f"{rep['status']},,,,,{reason}")
    print_fn(f"# {n_ok} compiled cells")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
