"""tick_host_ms.chat: mean over the ticks in the window of the tick's time
minus the time it waits on the device for first tokens and decoded
tokens (``serve.tick`` less its ``serve.prefill.wait`` and
``serve.decode.wait`` spans)."""
from program_spans import tick_host_ms as read  # noqa: F401
