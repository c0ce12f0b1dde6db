"""Share of the window in which no operation ran on the device, in the
backlog cells."""
from benchlib.readers import idle_share as read  # noqa: F401
