"""idle_share.chat: 100 x (1 - device-busy union / traced window)."""
from readers import idle_share_pct as read  # noqa: F401
