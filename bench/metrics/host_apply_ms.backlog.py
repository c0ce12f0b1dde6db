"""Host milliseconds per tick after the tick's last ``serve.wait``:
banking tokens and retiring rows, in the backlog cells."""
from benchlib.serve_spans import host_apply_ms as read  # noqa: F401
