"""stages.device_ms.sharded4: device milliseconds per product spent in the
protocol's stage programs (``ShardedCMPC``'s ``step`` and the decode), on
the busiest chip of the mesh."""
from bench.harness.readers import stage_ms_per_product as read  # noqa: F401
