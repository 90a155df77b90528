"""Communication that the ring does not hide: on chip 0, the time
inside collective operations during which no other operation runs on
the core, over the time of the calls (the programs that hold them)."""

from benchmark.reduce import trace


def read(ctx):
    pattern = ctx["cell"].config.get("collective_op_pattern")
    if not pattern or ctx["facts"].get("chips", 1) < 2:
        return None
    plane = ctx["planes"][0]
    ops = trace.select(ctx["events"], plane, trace.OPS)
    import re

    rx = re.compile(pattern)
    comm = trace.union(trace.intervals([e for e in ops if rx.search(e.name)]))
    if not comm:
        return None
    compute = trace.union(
        trace.intervals([e for e in ops if not rx.search(e.name)]))
    exposed = trace.total(trace.subtract(comm, compute))
    calls = trace.total(trace.union(trace.intervals(
        trace.select(ctx["events"], plane, trace.MODULES))))
    if calls <= 0:
        return None
    print(f"dist.exposed_comm_share: collectives {trace.total(comm):.4f} s, "
          f"exposed {exposed:.4f} s of {calls:.4f} s in calls")
    return 100.0 * exposed / calls
