"""Share of the paged-attention kernel's grid steps along rows and the
table that fetch and compute: the ``steps`` counter over ``grid`` of the
window's ``serve.dispatch`` spans. A program whose dispatch spans carry no
such counters gives None."""
from __future__ import annotations

from typing import Optional

from benchlib.serve_spans import window


def read(run) -> Optional[float]:
    ticks = window(run)
    if ticks is None:
        return None
    steps = grid = 0
    for tk in ticks:
        for d in tk.named("serve.dispatch"):
            if "grid" not in d.attrs:
                return None
            steps += d.attrs["steps"]
            grid += d.attrs["grid"]
    return steps / grid * 100.0 if grid else None
