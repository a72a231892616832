"""mesh.collective_share: percent of the busiest device's operation time
spent in cross-chip collectives (all-gather, all-reduce, collective-permute,
reduce-scatter, all-to-all, and their async start and done halves), by the
kind the compiled HLO gives each op.  In the flow-sharded sweep these are
the per-tick all-gather of the link sums and the psum of the settle
predicate; they move points_per_s."""

COLLECTIVES = ("all-gather", "all-reduce", "collective-permute",
               "reduce-scatter", "all-to-all")


def read(trace):
    if len(trace.ops) < 2:
        return None
    seconds = trace.kind_seconds(COLLECTIVES, trace.busiest())
    if seconds is None or seconds[1] <= 0:
        return None
    return 100.0 * seconds[0] / seconds[1]
