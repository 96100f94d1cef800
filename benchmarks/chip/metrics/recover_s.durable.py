"""recover_s.durable: host time of ``recover`` on the benchmark's own
``CXL0Context``, for the recoveries inside the window."""
from readers import recover_s as read  # noqa: F401
