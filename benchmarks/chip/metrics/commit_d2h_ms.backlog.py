"""commit_d2h_ms.backlog: mean over the session commits in the window of
the time spent reading running slots' lanes to the host
(``serve.commit.d2h`` spans inside ``serve.commit``)."""
from program_spans import commit_d2h_ms as read  # noqa: F401
