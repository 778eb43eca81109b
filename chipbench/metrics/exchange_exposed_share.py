"""Share of the device's busy time in which a collective runs and no other
operation does, in percent, averaged over the cell's chips: the exchange
that no compute hides (``trace_reduce.exposed_collective_ns``, per chip over
its busy time).

Returns None where the trace holds no collective at all (nothing to read):
every sharded program sums its chips' partial counts by one, so a trace
without any means the names were not found, not that the exchange is free.
"""


def read(run):
    red = run["trace"]
    if not red or not red.get("collective_ops"):
        return None
    shares = [100.0 * exposed / busy
              for exposed, busy in zip(red["collective_exposed_s"], red["busy_s"]) if busy > 0]
    return sum(shares) / len(shares) if shares else None
