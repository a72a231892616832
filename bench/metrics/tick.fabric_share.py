"""tick.fabric_share: percent of the device's operation time under the
engine's `tick/fabric` scope: `topology.shared_fabric_tick`, the per-link
sums and fluid FIFO (`link_sums`), the ECN and drop signals (`signals`),
the delivery ring (`delivery`) and the feedback (`feedback`).  Read from
the compiled HLO's `op_name` metadata (`bench/scopes.py`); moves
points_per_s."""


def read(trace):
    shares = trace.shares()
    return None if shares is None else shares["tick.fabric_share"]
