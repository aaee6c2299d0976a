"""sharded.host_mib_per_product: MiB that cross between the host and the
chips per completed product in the sharded runner: each block's I points
brought to the host and their first N rows sent back to the decode's chip.
Read from the program's ``host_bytes`` counter (``ShardedCMPC.run``, via
``MPCSession.stats``); ``None`` where the program has no such counter."""


def read(run):
    host_bytes = run.counters.get("host_bytes")
    if host_bytes is None or not run.completed:
        return None
    return host_bytes / 2**20 / run.completed
