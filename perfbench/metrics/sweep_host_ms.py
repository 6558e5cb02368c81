"""Host time per call outside the device run: the wall time of
``SweepSpec.run_fleetsim()`` minus its ``SweepResult.wall_clock_s``
(building params, re-lowering and loading the program, summarizing rows),
averaged over the window's untraced calls.

Layer: sweep API (``scenarios.SweepSpec``, ``fleetsim.sweep_grid``,
``metrics.summarize``).  Source: the host clock around the program's own
calls, less the device time the program reports.  Moves
``config_ticks_per_s``.
"""

LAYER = "sweep API"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "config_ticks_per_s"


def read(run):
    calls = [c for c in run.calls if not c.traced] or run.calls
    return 1e3 * sum(c.wall_s - c.device_s for c in calls) / len(calls)
