"""Least time for the ADC kernel's codes, ids and lookup tables at peak HBM
bandwidth over its device time, %."""
from chipbench.reduce import adc_roofline as read  # noqa: F401
