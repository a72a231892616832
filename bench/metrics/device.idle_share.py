"""device.idle_share: percent of the traced window in which no operation ran
on the device (averaged over the chips used).  Moves points_per_s: idle
device time is host work the sweep waits on."""


def read(trace):
    window = trace.window_s()
    if not trace.ops or window <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s() / window)
