"""device_idle_share: the percentage of the traced window in which no
operation ran on the device, averaged over the chips the cell uses.

Layer: device.  Busy time is the union of the device's operation
intervals inside the benchmark's ``window`` annotation.  None where the
trace holds no device activity.
"""


def read(ctx):
    t = ctx.trace
    if t.window_s <= 0 or t.busy_s <= 0:
        return None
    return (1.0 - t.busy_s / t.window_s) * 100.0
