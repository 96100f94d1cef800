"""commit_stall_ms.durable: mean blocking time of the commit regions in the
window (what ``StepTiming.commit_s`` times)."""
from readers import commit_stall_ms as read  # noqa: F401
