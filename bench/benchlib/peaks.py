"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

The table is ``bench/peaks.json``. A device that is not in it is an error,
never a default: a roofline share against a guessed peak means nothing."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parents[1] / "peaks.json"


class UnknownDevice(KeyError):
    pass


def peaks_for(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise UnknownDevice(
            f"no peaks for device_kind {device_kind!r} in {path.name}; "
            f"known: {sorted(table)}")
    return table[device_kind]


def matmul_peak(peaks: dict, precision: str) -> float:
    """Peak operations/s of the configuration's matmul dtype."""
    return peaks["int8_ops_per_s"] if precision == "w8a8" \
        else peaks["bf16_flops_per_s"]
