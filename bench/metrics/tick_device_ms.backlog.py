"""Device-busy milliseconds of the window per tick, in the backlog cells."""
from benchlib.readers import tick_device_ms as read  # noqa: F401
