"""Share of the window's waves whose batch-wide refresh gate opened (any
turn of the wave refreshed: TurnRecord.wave and .refreshed), %."""
from chipbench.spans import gate_open_share as read  # noqa: F401
