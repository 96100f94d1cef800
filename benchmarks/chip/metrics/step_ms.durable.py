"""step_ms.durable: mean host time of a train step in the window, from the
feed's call to the hand-off of its state (after ``float(loss)``)."""
from readers import step_ms as read  # noqa: F401
