"""Host milliseconds per tick before the tick's first ``serve.wait``:
retire, SLOs, admission, plan, input assembly and dispatch, in the backlog
cells."""
from benchlib.serve_spans import host_prep_ms as read  # noqa: F401
