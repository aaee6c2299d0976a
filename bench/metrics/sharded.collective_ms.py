"""sharded.collective_ms: device milliseconds per product spent in the
collective operations of the sharded exchange (``ShardedCMPC``'s
reduce-scatter, and any all-reduce, all-gather, all-to-all or
collective-permute), on the chip where they took longest."""
from bench.harness.readers import collective_ms_per_product as read  # noqa: F401
