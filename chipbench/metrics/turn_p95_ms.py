"""95th percentile of the window's per-turn latency, scheduled send time to
result, over every turn due in it (window.summary), ms."""
from chipbench.reduce import turn_p95_ms as read  # noqa: F401
