"""sharded.host_mib_per_product: MiB that cross between the host and the
chips per completed product in the sharded runner.  Since the runner keeps
each block's I points on the chips and copies only the decode quorum's
rows chip to chip (``ShardedCMPC.gather_quorum``), this reads 0; a share
brought back through the host would show here.  Read from the program's
``host_bytes`` counter (``ShardedCMPC.run``, via ``MPCSession.stats``);
``None`` where the program has no such counter."""


def read(run):
    host_bytes = run.counters.get("host_bytes")
    if host_bytes is None or not run.completed:
        return None
    return host_bytes / 2**20 / run.completed
