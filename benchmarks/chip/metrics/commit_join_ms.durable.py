"""commit_join_ms.durable: mean over the commits in the window of the
time each waits for the previous commit's flush (``dsm.commit.join``
spans inside ``dsm.commit``; 0 for a commit with nothing in flight)."""
from program_spans import commit_join_ms as read  # noqa: F401
