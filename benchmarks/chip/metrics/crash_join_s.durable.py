"""crash_join_s.durable: seconds the crashes in the window spend joining
the aborted flush before recovery starts (``dsm.crash.join`` spans
inside ``train.crash``)."""
from program_spans import crash_join_s as read  # noqa: F401
