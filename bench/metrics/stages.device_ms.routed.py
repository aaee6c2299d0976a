"""stages.device_ms.routed: device milliseconds per routed request (one
projection over the chip's experts) spent in the protocol's stage
programs, on the busiest chip."""
from bench.harness.readers import stage_ms_per_product as read  # noqa: F401
