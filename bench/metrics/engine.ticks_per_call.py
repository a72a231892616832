"""engine.ticks_per_call: the engine ticks a traced call ran, from the runs
of the early-exit chunk loops (`engine/chunk`) on the busiest device, each
as many ticks as the trip count its compiled HLO states, over the traced
calls (`bench/scopes.py`).  The early exit stops a call once every point
settled, so each tick it saves moves points_per_s."""


def read(trace):
    return trace.ticks_per_call()
