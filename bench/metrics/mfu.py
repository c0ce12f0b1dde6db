"""Model operations of the fed tokens per second of the traced window over
the matmul dtype's peak."""
from benchlib.readers import mfu as read  # noqa: F401
