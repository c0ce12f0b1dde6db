"""Readings that set a cell's ``max_gap`` limit: the program's and the
control's, on several seeds in one process.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 20

Each seed runs the cell as ``bench/run.py`` does (its sizes, its load, a
window of ``--seconds``), then reads the widest gap of the served tokens
and, on the same prompts and tokens, that of the control: the reference
computed in the precision below the configuration's (int4 for W8A8), whose
first choice at each position is scored against the float32 reference.
Both are judged by the same limit (``check.verdict``): one JSON line per
seed with the program's ``correct``, which should be true, and the
control's ``control_correct``, which has to be false. The benchmark's own
runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import run


def main(argv=None, root: Path = run.BENCH.parent) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    spec = run.Spec.load(Path(root), args.workload)
    import jax
    try:
        devices = run.check_devices(jax, spec.cell["chips"])
        peaks = run.peaks_for(devices[0].device_kind)
    except (run.NoAccelerator, run.UnknownDevice) as e:
        print(f"control: {e}", file=sys.stderr)
        return 1
    sys.path.insert(0, str(run.BENCH.parent / "src"))
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run.run_cell(spec, seed, args.seconds, False, devices, peaks,
                           control=True)
        res, ctl = out["result"], out["control"]
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "correct": res["correct"], "control_correct": ctl["correct"],
            "checks": dict(res["checks"], control_max_gap={
                "value": ctl["max_gap"],
                "limit": res["checks"]["max_gap"]["limit"]}),
            "metrics": res["metrics"],
            "compared_requests": out["info"]["compared_requests"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
