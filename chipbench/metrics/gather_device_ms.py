"""Device time of the session gather (SessionStore.gather, eager jit_gather
programs, outside step_batch) per wave in the traced window, ms."""
from chipbench.reduce import gather_device_ms as read  # noqa: F401
