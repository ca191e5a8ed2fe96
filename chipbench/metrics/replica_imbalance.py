"""The busiest replica's share of the window's turns over the mean
replica's (TurnRecords by the engine that served them), %: 100 is even."""
from chipbench.reduce import replica_imbalance as read  # noqa: F401
