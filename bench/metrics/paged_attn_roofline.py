"""Roofline time of the paged-attention kernel's needed bytes and flops
over its device time."""
from benchlib.readers import paged_attn_roofline as read  # noqa: F401
