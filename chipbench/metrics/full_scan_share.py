"""Share of the window's turns that scored all p centroids (first turns and
refreshes), %."""
from chipbench.reduce import full_scan_share as read  # noqa: F401
