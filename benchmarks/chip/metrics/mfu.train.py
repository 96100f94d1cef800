"""mfu.train: model FLOPs per token (forward and backward, no
recomputation) times the tokens/s the job advanced, over the bf16 peak."""
from readers import train_mfu_pct as read  # noqa: F401
