"""commit_snapshot_ms.durable: mean over the commits in the window of the
host snapshot each takes of the state it flushes (the device-to-host
gather; ``dsm.commit.snapshot`` spans inside ``dsm.commit``)."""
from program_spans import commit_snapshot_ms as read  # noqa: F401
