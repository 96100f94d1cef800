"""tick_ms.backlog: mean wall time of the ticks in the window that did
not commit."""
from readers import tick_ms as read  # noqa: F401
