"""device.idle_share.routed: percent of the traced window in which no
operation ran on the device, mean over the cell's chips (routed-expert
cells)."""
from bench.harness.readers import idle_share as read  # noqa: F401
