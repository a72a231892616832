"""tick.scatter_share: percent of the device's operation time spent in
scatters, fused or not, by the kind the compiled HLO gives each op (on a
TPU a scatter runs as `fusion.<n>` with a scatter at its root).  In the
fabric tick these are `topology._link_sum` (the per-link segment sums) and
`topology.scatter_delivery` (the delivery ring); they move points_per_s."""


def read(trace):
    seconds = trace.kind_seconds("scatter")
    if seconds is None or seconds[1] <= 0:
        return None
    return 100.0 * seconds[0] / seconds[1]
