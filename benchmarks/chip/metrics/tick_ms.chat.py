"""tick_ms.chat: mean wall time of the ticks in the window (none
commits: the chat mix has no pool)."""
from readers import tick_ms as read  # noqa: F401
