"""Host milliseconds per tick of the engine's step (its span less the
device time inside it), in the backlog cells."""
from benchlib.readers import host_ms_per_tick as read  # noqa: F401
