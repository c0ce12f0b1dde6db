"""Roofline time of the int8 matmuls at the real tokens fed over their
device time."""
from benchlib.readers import int8_matmul_roofline as read  # noqa: F401
