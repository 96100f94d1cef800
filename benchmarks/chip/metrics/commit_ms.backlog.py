"""commit_ms.backlog: mean wall time of committing ticks minus
``tick_ms.backlog``."""
from readers import commit_ms as read  # noqa: F401
