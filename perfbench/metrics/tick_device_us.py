"""Device time of one grid tick: the busiest chip's busy time in the
traced call over the ticks it advanced every row of the grid.

Layer: tick program (``fleetsim/stages.py``, run by ``engine`` or
``fused``).  Source: the profiler trace.  Moves ``config_ticks_per_s``:
at a fixed grid, the window's rate is rows / this time, less idle time.
"""

LAYER = "tick program"
UNIT = "us"
SOURCE = "device_trace"
MOVES = "config_ticks_per_s"


def read(run):
    if run.trace is None:
        return None
    traced = sum(1 for c in run.calls if c.traced)
    busy = run.trace.busy_s[run.trace.busiest]
    return busy / (traced * run.cell.n_ticks) * 1e6
