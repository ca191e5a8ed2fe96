"""95th percentile of the window's turns' queue wait
(TurnRecord.queue_wait_s, enqueue to launch), ms."""
from chipbench.reduce import queue_wait_p95_ms as read  # noqa: F401
