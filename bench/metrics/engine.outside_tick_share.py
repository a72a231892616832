"""engine.outside_tick_share: percent of the device's operation time under
no `tick` scope: the engine round the tick (`engine/keys`, the chunk loop's
own ops, `engine/settle`, `engine/result`), the sweep's set-up, and ops the
compiled HLO names no scope of.  With the three `tick.*_share` it
partitions the op time (`bench/scopes.py`); moves points_per_s."""


def read(trace):
    shares = trace.shares()
    return None if shares is None else shares["engine.outside_tick_share"]
