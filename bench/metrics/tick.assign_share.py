"""tick.assign_share: percent of the device's operation time under the
engine's `tick/assign` scope: `policies.assign_paths`, every branch of the
policy switch (`tick/assign/<policy>`, each run for every point under
`vmap`), with WAM's and RAND_ADAPTIVE's `searchsorted` loops.  Read from
the compiled HLO's `op_name` metadata (`bench/scopes.py`); moves
points_per_s."""


def read(trace):
    shares = trace.shares()
    return None if shares is None else shares["tick.assign_share"]
