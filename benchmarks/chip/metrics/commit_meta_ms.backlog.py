"""commit_meta_ms.backlog: mean over the session commits in the window of
the time spent on metadata: version walks for new block names, the
session and block tables, the manifest and gc (``dsm.lstore.seed``,
``serve.commit.table``, ``dsm.commit.manifest``, ``dsm.commit.gc`` spans
inside ``serve.commit``)."""
from program_spans import commit_meta_ms as read  # noqa: F401
