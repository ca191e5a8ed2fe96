"""Least time for the probed lists' real rows at peak HBM bandwidth over
step_batch device time, in the overload cell, %."""
from chipbench.reduce import step_roofline as read  # noqa: F401
