"""Share of the traced window in which the chip ran no operation.

Layer: device.  Source: the profiler trace of one window call (union of
the device's operation intervals, ``trace_reduce``); on several chips the
largest share over them.  Moves ``config_ticks_per_s``: every idle second
is host work the chip waits on.
"""

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "config_ticks_per_s"


def read(run):
    if run.trace is None:
        return None
    return 100.0 * max(run.trace.idle_share(d) for d in run.trace.busy_s)
