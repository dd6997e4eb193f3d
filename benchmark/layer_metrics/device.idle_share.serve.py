"""Device: share of the traced window in which no operation ran (serving cells)."""
from benchmark.harness.layers import idle_share as read  # noqa: F401
