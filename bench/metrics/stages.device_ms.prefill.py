"""stages.device_ms.prefill: device milliseconds per product spent in the
protocol's stage programs (``fused``, ``front``, ``decode``, ``tags`` and
their vmapped twins, the sharded ``step``), on the busiest chip."""
from bench.harness.readers import stage_ms_per_product as read  # noqa: F401
