"""tick.gather_share: percent of the device's operation time spent in
gathers, fused or not, by the kind the compiled HLO gives each op.  In the
tick these are the route and ring lookups and the path-assignment
searches (`policies`' `jnp.searchsorted`, under every branch of the
policy switch); they move points_per_s."""


def read(trace):
    seconds = trace.kind_seconds("gather")
    if seconds is None or seconds[1] <= 0:
        return None
    return 100.0 * seconds[0] / seconds[1]
