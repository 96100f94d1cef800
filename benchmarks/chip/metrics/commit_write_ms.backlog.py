"""commit_write_ms.backlog: mean over the session commits in the window of
the time spent writing and fsyncing block objects (``dsm.flush.write``
and ``dsm.flush.fsync`` spans inside ``serve.commit``)."""
from program_spans import commit_write_ms as read  # noqa: F401
