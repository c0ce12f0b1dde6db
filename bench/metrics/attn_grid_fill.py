"""Share of the paged-attention grid's steps that read a block a fed row
holds: the ``blocks`` counter over ``B`` x ``width`` of the window's
``serve.dispatch`` spans."""
from benchlib.serve_spans import attn_grid_fill as read  # noqa: F401
