"""Share of the device-busy time inside the window's ``serve.step`` spans
that falls in ticks with T > 1, in the backlog cells."""
from benchlib.serve_spans import prefill_tick_share as read  # noqa: F401
