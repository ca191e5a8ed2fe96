"""Real rows over launched rows, padding included, of the window's launches
(MicroBatcher.batch_sizes / padded_sizes), %."""
from chipbench.reduce import batch_fill as read  # noqa: F401
