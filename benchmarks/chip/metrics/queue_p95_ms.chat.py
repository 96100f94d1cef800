"""queue_p95_ms.chat: p95 over the admissions in the window of the time a
request waited between the engine's ``submit`` and its admission to a
slot (``queued_ms`` of ``serve.admit`` spans)."""
from program_spans import queue_p95_ms as read  # noqa: F401
