"""tick.sender_share: percent of the device's operation time under the
engine's `tick/sender` scope: the emit budget, the policy state, the
retransmission debt, the controller's `cond` and the completion test.  Read
from the compiled HLO's `op_name` metadata (`bench/scopes.py`); moves
points_per_s."""


def read(trace):
    shares = trace.shares()
    return None if shares is None else shares["tick.sender_share"]
