"""device.idle_share.sharded4: percent of the traced window in which no
operation ran on a chip, mean over the cell's chips (cells on a mesh)."""
from bench.harness.readers import idle_share as read  # noqa: F401
