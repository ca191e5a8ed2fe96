"""Mean device time of one step_batch execution in the traced window, ms."""
from chipbench.reduce import step_device_ms as read  # noqa: F401
