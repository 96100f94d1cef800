"""mfu.backlog: FLOPs of every prompt and output token processed in
the window, over the window, over the bf16 peak."""
from readers import serve_mfu_pct as read  # noqa: F401
